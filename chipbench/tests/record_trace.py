"""Record the small trace that test_trace.py reduces, on the accelerator.

    python3 chipbench/tests/record_trace.py <out.json.gz>

Inside the window marks: three annotated steps of a few device
operations, with host sleeps of known length between them, so that the
trace has device time, idle gaps and host annotations to name them.
Only the window's events are kept, as ``trace.Events`` in gzipped JSON.
"""
from __future__ import annotations

import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace as tracing  # noqa: E402

SLEEP_S = (0.02, 0.05, 0.03)


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        print("record_trace: needs an accelerator", file=sys.stderr)
        return 3
    ann = jax.profiler.TraceAnnotation
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: (a @ a) * 0.5)
    mm(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=tracing.profiler_options())
    with ann(tracing.OPEN):
        pass
    for i, pause in enumerate(SLEEP_S):
        with ann("record.step"):
            y = x
            for _ in range(4):
                y = mm(y)
            y.block_until_ready()
        with ann(f"record.sleep{i}"):
            time.sleep(pause)
    with ann(tracing.CLOSE):
        pass
    jax.profiler.stop_trace()
    ev = tracing.load_events(tracing.find_xplane(d))
    lo = min(s for n, s, _ in ev.host if n == tracing.OPEN)
    hi = max(s + dur for n, s, dur in ev.host if n == tracing.CLOSE)

    def inside(e):
        return e[1] < hi and e[1] + e[2] > lo

    kept = tracing.Events(
        {p: [e for e in ops if inside(e)] for p, ops in ev.device_ops.items()},
        [e for e in ev.host if inside(e) and (
            e[0].startswith(("record.", "chipbench.")) or e[2] > 1e5)])
    tracing.save_events(kept, out)
    s = tracing.summarize(kept)
    print(f"busy {s.busy_s} s of {s.window_s} s; {s.breakdown}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
