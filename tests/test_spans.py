"""Program spans and the per-iteration counters they time: swap bytes,
the split of an iteration's wall time into disjoint parts, the
controller's time between iterations, and the ``tensile.*`` spans in a
profiler trace recorded on the CPU."""
import gc as pygc
import glob
import time

import jax
import pytest
from jax.extend import core as jcore

from repro.core import (GlobalController, JaxprExecutor, MachineProfile,
                        MemoryEngine, SchedulerConfig, SchedulingPlan,
                        analyze, build_pipeline, find_safe_points,
                        schedule_single)
from repro.core.telemetry import TelemetryHub
from repro.obs import SPANS, gc_spans
from repro.service import JobSpec

from helpers import capture_mlp, mlp_params, mlp_train_step

PROFILE = MachineProfile(host_link_bw=16e9, compute_flops=5e10, mem_bw=1e10)


@pytest.fixture(scope="module")
def mlp():
    return capture_mlp(sizes=(64, 256, 256, 256, 8), batch=32)


def _parts(st):
    return st.dispatch_s + st.sync_s + st.transfer_s + st.stall_time_s


@pytest.mark.parametrize("async_swap", [False, True])
def test_swap_bytes_and_wall_time_split(mlp, async_swap, monkeypatch):
    seq, closed, args = mlp
    plan = schedule_single(seq, profile=PROFILE).plans[seq.job_id]
    # every swap-in copies its storage from the host store once
    fetched = []
    fetch = JaxprExecutor._host_fetch

    def host_fetch(self, st):
        fetched.append(st)
        return fetch(self, st)

    monkeypatch.setattr(JaxprExecutor, "_host_fetch", host_fetch)
    hub = TelemetryHub()
    host, swapped = {}, 0
    for it in range(3):
        eng = MemoryEngine(PROFILE, trace=True)
        ex = JaxprExecutor(closed, seq, plan, engine=eng,
                           async_swap=async_swap, telemetry=hub,
                           iteration=it)
        ex.host.update(host)
        before = set(ex.host)
        fetched.clear()
        ex.run(*args)
        ex.close()
        st, size = ex.stats, ex.ctx.size_of
        out = [r.storage for r in eng.trace.records
               if r.action == "swap_out"]
        ins = list(fetched)
        assert st.swap_out_count == len(out)
        assert st.swap_out_bytes == sum(size(s) for s in out)
        assert st.swap_in_count == len(ins)
        assert st.swap_in_bytes == sum(size(s) for s in ins)
        assert set(ins) <= before | set(out)
        # a storage's size is the bytes its host copy holds
        for s in set(out) & set(ex.host):
            assert ex.host[s].nbytes == size(s)
        swapped += len(out)
        # the parts are disjoint: they sum to the wall time, and what is
        # left, the executor's own work, is not negative
        assert st.self_s >= 0.0
        assert _parts(st) + st.self_s == pytest.approx(st.wall_time_s,
                                                       rel=1e-9, abs=1e-12)
        assert st.dispatch_s > 0.0 and st.sync_s > 0.0
        if not async_swap and out:
            assert st.transfer_s > 0.0
        host = ex.host
    assert swapped > 0


def test_executor_without_hub_does_not_sync(mlp):
    seq, closed, args = mlp
    ex = JaxprExecutor(closed, seq, None)
    ex.run(*args)
    assert ex.stats.sync_s == 0.0 and ex.stats.dispatch_s > 0.0
    assert ex.stats.self_s >= 0.0


def _payload(seed=0):
    p = mlp_params(jax.random.PRNGKey(seed), [32, 128, 128, 4])
    from repro.optim.adam import adamw_init
    b = (jax.random.normal(jax.random.PRNGKey(seed + 1), (8, 32)),
         jax.random.normal(jax.random.PRNGKey(seed + 2), (8, 4)))
    return mlp_train_step, p, adamw_init(p), b


def _controller(async_swap):
    # a zero drift threshold: every iteration's measured latencies
    # trigger a replan
    return GlobalController(
        profile=PROFILE, async_swap=async_swap,
        scheduler_config=SchedulerConfig(update_threshold=0.0,
                                         memory_budget_bytes=40_000))


def test_controller_time_between_iterations():
    ctl = _controller(async_swap=False)
    h = ctl.submit(JobSpec("j", iterations=3, payload=_payload()))
    ctl.wait(timeout=300)
    assert h.error is None and len(h.stats) == 3
    first, second, third = h.stats
    assert first.before_s == 0.0 and first.replans == 0
    # the first iteration's op samples are cold (they compile), so the
    # hub reports no drift after it and nothing is replanned
    assert second.before_s > 0.0
    assert second.replans == 0 and second.replan_s == 0.0
    assert third.replans >= 1
    assert 0.0 < third.replan_s <= third.before_s
    for st in (second, third):
        assert st.self_s >= 0.0
        assert _parts(st) + st.self_s == pytest.approx(st.wall_time_s)


def _span_names(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events
                             if e.name.startswith("tensile."))
    return names


def test_profiler_trace_holds_the_program_spans(tmp_path):
    """A controller job with swaps on the worker thread, traced by the
    profiler: every span the path reaches is in the trace under its
    name.  (Recomputes, prefetch waits and plan hot-swaps come only with
    plans that have them.)"""
    ctl = _controller(async_swap=True)
    with jax.profiler.trace(str(tmp_path)):
        h = ctl.submit(JobSpec("j", iterations=3, payload=_payload(3)))
        while not h.done:
            pygc.collect()
            time.sleep(0.01)
        ctl.wait(timeout=300)
    assert h.error is None
    assert sum(st.swap_out_count for st in h.stats) > 0
    names = _span_names(tmp_path)
    assert names <= set(SPANS)
    assert set(SPANS) - names <= {"tensile.recompute", "tensile.hot_swap"}


def test_profiler_trace_holds_recompute_and_hot_swap_spans(mlp, tmp_path):
    """The two spans a controller job reaches only with plans that have
    them: a recompute (an activation released before its next use) and a
    plan hot-swapped in at a safe point."""
    seq, closed, (params, opt, batch) = mlp
    ex = JaxprExecutor(closed, seq, None)
    used = {ex._name_of(v): j for j, eqn in enumerate(ex.jaxpr.eqns)
            for v in eqn.invars if not isinstance(v, jcore.Literal)}
    tid, producer = next((nm, i) for nm, i in ex.producer.items()
                         if used.get(nm, i) > i + 1)
    early = SchedulingPlan(job_id=seq.job_id)
    early.set_release(tid, producer)

    prior = SchedulingPlan(job_id=seq.job_id)
    safe = find_safe_points(seq, prior)
    newp = build_pipeline("tensile+autoscale", profile=PROFILE,
                          config=SchedulerConfig()).replan_from(
        [seq], {seq.job_id: prior}, {seq.job_id: safe[0].op_idx},
        budgets={seq.job_id: int(analyze([seq]).peak_bytes * 0.7)}
    ).plans[seq.job_id]

    with jax.profiler.trace(str(tmp_path)):
        rec = JaxprExecutor(closed, seq, early)
        rec.run(params, opt, batch)
        hot = JaxprExecutor(closed, seq, prior)
        hot.request_plan(newp, {sp.op_idx for sp in safe})
        hot.run(params, opt, batch)
    assert rec.stats.recompute_count >= 1 and hot.stats.hot_swaps == 1
    assert {"tensile.ensure", "tensile.recompute",
            "tensile.hot_swap"} <= _span_names(tmp_path)


def test_gc_spans_register_while_in_use():
    base = list(pygc.callbacks)
    with gc_spans():
        with gc_spans():
            assert len(pygc.callbacks) == len(base) + 1
        pygc.collect()
        assert len(pygc.callbacks) == len(base) + 1
    assert pygc.callbacks == base
