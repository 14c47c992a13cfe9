"""Share of the window (%) the controller spent replanning between the
window's steps (ExecutionStats.replan_s, host clock); 0 in a window
without a replan: planner layer."""


def read(run):
    if getattr(run, "kind", None) != "train" or not run.window_s:
        return None
    return 100.0 * sum(s.replan_s for s in run.stats) / run.window_s
