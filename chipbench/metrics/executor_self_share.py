"""Share of the window (%) the executor spent on its own bookkeeping, the
rest of its wall time (ExecutionStats.self_s, host clock): executor
layer."""


def read(run):
    if getattr(run, "kind", None) != "train" or not run.window_s:
        return None
    return 100.0 * sum(s.self_s for s in run.stats) / run.window_s
