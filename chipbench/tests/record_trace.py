"""Record the small trace that test_trace.py reduces, on the accelerator.

    python3 chipbench/tests/record_trace.py <out.json.gz>

Inside the window marks: three annotated steps, with host sleeps of known
length between them, so that the trace has device time, idle gaps and
host annotations to name them.  Each step runs a jitted scan over four
layers whose parts are traced under ``jax.named_scope("attn")`` and
``"mlp"``, as a model's layer stack is, then binds the equations of a
small jaxpr one by one, as the program's executor does, with the first
of them under ``"eager"``.  Only the window's events are kept, as
``trace.Events`` in gzipped JSON.
"""
from __future__ import annotations

import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace as tracing  # noqa: E402

SLEEP_S = (0.02, 0.05, 0.03)


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from repro.core.executor import _eval_eqn

    if jax.devices()[0].platform == "cpu":
        print("record_trace: needs an accelerator", file=sys.stderr)
        return 3
    ann = jax.profiler.TraceAnnotation

    def layer(a, _):
        with jax.named_scope("attn"):
            a = jnp.tanh(a @ a)
        with jax.named_scope("mlp"):
            a = (a @ a) * 0.5
        return a, None

    def small(a):
        with jax.named_scope("eager"):
            a = a @ a
        return a * 0.5

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    stack = jax.jit(lambda a: jax.lax.scan(layer, a, None, length=4)[0])
    closed = jax.make_jaxpr(small)(x)

    def eager(a):
        env = dict(zip(closed.jaxpr.invars, [a]))
        for eqn in closed.jaxpr.eqns:
            outs = _eval_eqn(eqn, [env[v] for v in eqn.invars])
            env.update(zip(eqn.outvars, outs))
        return env[closed.jaxpr.outvars[0]]

    jax.block_until_ready((stack(x), eager(x)))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=tracing.profiler_options())
    with ann(tracing.OPEN):
        pass
    for i, pause in enumerate(SLEEP_S):
        with ann("record.step"):
            jax.block_until_ready(eager(stack(x)))
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
    print(f"scope_seconds {s.scope_seconds}")
    for p, ops in kept.device_ops.items():
        for e in ops:
            print(p, e)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
