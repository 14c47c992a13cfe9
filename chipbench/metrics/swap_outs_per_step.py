"""Swap-outs the executor made per window step (ExecutionStats counts):
executor layer."""


def read(run):
    swaps = getattr(run, "swap_outs", None)
    return sum(swaps) / len(swaps) if swaps else None
