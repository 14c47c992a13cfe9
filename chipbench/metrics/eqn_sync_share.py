"""Share of the window (%) the executor spent waiting for each equation's
results (ExecutionStats.sync_s, host clock): executor layer."""


def read(run):
    if getattr(run, "kind", None) != "train" or not run.window_s:
        return None
    return 100.0 * sum(s.sync_s for s in run.stats) / run.window_s
