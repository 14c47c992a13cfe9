"""Bytes swapped out and in per window step, GiB (ExecutionStats
swap_out_bytes + swap_in_bytes, storage sizes): transfer layer."""


def read(run):
    if getattr(run, "kind", None) != "train" or not run.stats:
        return None
    moved = sum(s.swap_out_bytes + s.swap_in_bytes for s in run.stats)
    return moved / len(run.stats) / 2 ** 30
