"""Model FLOP utilisation of the whole training step (%): operations the
step needs per token (forward and backward, from the configuration's
shapes, no recomputation) times tokens per second, over the chip's bf16
peak in peaks.json."""


def read(run):
    rate = getattr(run, "train_tokens_per_s", None)
    if not rate:
        return None
    return 100.0 * run.flops_per_token * rate / (run.peak_flops * run.chips)
