"""Share of the window (%) the controller spent between the window's
steps, replanning left out (ExecutionStats.before_s - replan_s, host
clock): controller layer."""


def read(run):
    if getattr(run, "kind", None) != "train" or not run.window_s:
        return None
    return 100.0 * sum(s.before_s - s.replan_s for s in run.stats) \
        / run.window_s
