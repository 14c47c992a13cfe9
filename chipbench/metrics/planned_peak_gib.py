"""The device peak the planner planned for the step (GiB), from the plan
the controller ran in the window: planner layer."""


def read(run):
    peak = getattr(run, "planned_peak_bytes", None)
    return None if not peak else peak / 2 ** 30
