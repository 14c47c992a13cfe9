"""Share of the window (%) the executor spent waiting for operands that
were not on the device (ExecutionStats.stall_time_s, host clock): executor
layer."""


def read(run):
    stalls = getattr(run, "stall_s", None)
    if not stalls or not run.window_s:
        return None
    return 100.0 * sum(stalls) / run.window_s
