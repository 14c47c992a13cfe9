"""Share of the traced training window (%) in which no operation ran on
the device: device layer."""


def read(run):
    if getattr(run, "kind", None) != "train" or run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
