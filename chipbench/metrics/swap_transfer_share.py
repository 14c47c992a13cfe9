"""Share of the window (%) the executor's thread spent inside the DMA
channel moving swaps, where no stall counts it (ExecutionStats.transfer_s,
host clock): transfer layer."""


def read(run):
    if getattr(run, "kind", None) != "train" or not run.window_s:
        return None
    return 100.0 * sum(s.transfer_s for s in run.stats) / run.window_s
