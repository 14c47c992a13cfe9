"""Cold op samples: a job's first execution of each equation traces and
compiles it, so its measured latency is no op latency.  The executor
marks those samples cold, the hub keeps them in ``ops`` but out of the
EWMA latencies plans are made from, and the cost model does not
recalibrate from them."""
import time

import jax
import pytest
from jax.extend import core as jcore

import repro.core.executor as executor_mod
from repro.core import (CostModel, DeviceCalibration, EWMATracker,
                        GlobalController, JaxprExecutor, MachineProfile,
                        SchedulerConfig, SchedulingPlan, TelemetryHub)
from repro.service import JobSpec

from helpers import capture_mlp, mlp_params, mlp_train_step

PROFILE = MachineProfile(host_link_bw=16e9, compute_flops=5e10, mem_bw=1e10)
COMPILE_S = 0.02


@pytest.fixture(scope="module")
def mlp():
    return capture_mlp(sizes=(64, 128, 128, 8), batch=16)


def _payload(seed=0):
    from repro.optim.adam import adamw_init
    p = mlp_params(jax.random.PRNGKey(seed), [32, 64, 64, 4])
    b = (jax.random.normal(jax.random.PRNGKey(seed + 1), (8, 32)),
         jax.random.normal(jax.random.PRNGKey(seed + 2), (8, 4)))
    return mlp_train_step, p, adamw_init(p), b


# ------------------------------------------------------------------ hub
@pytest.mark.parametrize("buffered", [False, True])
def test_hub_keeps_cold_sample_out_of_latencies(buffered):
    hub = TelemetryHub()
    if buffered:
        hub.begin_buffering()
    hub.record_op("j", 0, 5.0, prim="dot_general", flops=1e6, cold=True)
    hub.flush()
    assert [s.cold for s in hub.ops["j"]] == [True]
    assert hub.op_latencies("j") == {}
    assert hub.latency_sum("j") == 0.0
    assert hub.drift_ratio("j", 1e-3) == 0.0
    # the first warm sample is the op's latency, untouched by the cold one
    hub.record_op("j", 0, 1e-3, prim="dot_general", flops=1e6)
    hub.flush()
    assert hub.op_latencies("j") == {0: 1e-3}
    assert hub.latency_sum("j") == 1e-3
    assert hub.drift_ratio("j", 1e-3) == 0.0
    assert hub.total_op_samples() == 2


def test_recalibrate_and_tracker_skip_cold_samples():
    calib = DeviceCalibration(flops=1e9, mem_bw=1e9)
    cm = CostModel(DeviceCalibration(flops=1e9, mem_bw=1e9))
    hub = TelemetryHub(clock="virtual")
    hub.record_op("j", 0, 10.0, prim="dot_general", flops=1e8,
                  bytes_accessed=1e3, cold=True)
    rep = cm.recalibrate(hub)
    assert (cm.calib.flops, cm.calib.mem_bw) == (calib.flops, calib.mem_bw)
    assert rep.samples == 1                   # the report still sees it
    tracker = EWMATracker()
    assert tracker.ingest(hub, "j") == 0 and tracker.values == {}
    # the same sample, warm, moves both
    hub.record_op("j", 0, 10.0, prim="dot_general", flops=1e8,
                  bytes_accessed=1e3)
    cm.recalibrate(hub, report=False)
    assert cm.calib.flops < calib.flops
    assert tracker.ingest(hub, "j") == 1 and tracker.values == {0: 10.0}


# ------------------------------------------------------------- executor
def test_dispatch_span_marks_first_binds_cold(mlp, monkeypatch):
    """Each equation's first bind in a job is cold, in the span and the
    counter; a second executor of the job given the same set binds none
    cold, and a recompute binds an equation the iteration already
    compiled."""
    seq, closed, args = mlp
    seen = []
    real_span = executor_mod.span

    def span(name, **kw):
        if name == "tensile.dispatch":
            seen.append(kw["cold"])
        return real_span(name, **kw)

    monkeypatch.setattr(executor_mod, "span", span)
    ex = JaxprExecutor(closed, seq, None)
    used = {ex._name_of(v): j for j, eqn in enumerate(ex.jaxpr.eqns)
            for v in eqn.invars if not isinstance(v, jcore.Literal)}
    tid, producer = next((nm, i) for nm, i in ex.producer.items()
                         if used.get(nm, i) > i + 1)
    early = SchedulingPlan(job_id=seq.job_id)
    early.set_release(tid, producer)
    n = len(closed.jaxpr.eqns)

    bound = set()
    first = JaxprExecutor(closed, seq, early, bound_eqns=bound)
    first.run(*args)
    assert first.stats.recompute_count >= 1
    assert first.stats.cold_ops == n and bound == set(range(n))
    assert seen.count(True) == n
    assert seen.count(False) == first.stats.recompute_count
    seen.clear()
    second = JaxprExecutor(closed, seq, early, bound_eqns=bound)
    second.run(*args)
    assert second.stats.cold_ops == 0 and not any(seen)


# ----------------------------------------------------------- controller
def test_controller_flags_only_the_first_iteration():
    """A job's first iteration flags every equation; the second, and the
    third under the plan the drift replan after the second made, flag
    none."""
    ctl = GlobalController(
        profile=PROFILE, async_swap=False,
        scheduler_config=SchedulerConfig(update_threshold=0.0,
                                         memory_budget_bytes=40_000))
    h = ctl.submit(JobSpec("j", iterations=3, payload=_payload()))
    ctl.wait(timeout=300)
    assert h.error is None
    n = len(h.closed_jaxpr.jaxpr.eqns)
    assert [st.cold_ops for st in h.stats] == [n, 0, 0]
    assert h.stats[2].replans >= 1             # a new plan version
    assert h.bound_eqns == set(range(n))
    samples = ctl.telemetry.ops["j"]
    assert sorted(s.op_idx for s in samples if s.cold) == list(range(n))
    assert {s.iteration for s in samples if s.cold} == {0}


def test_compile_time_stays_out_of_the_plan(monkeypatch):
    """With each equation's first bind made 20 ms slower, as a compile
    makes it, no replan follows the first iteration, and after the third
    neither the hub's latencies nor the job's sequence hold the delay."""
    bind = executor_mod._eval_eqn
    compiled = set()

    def eval_eqn(eqn, invals):
        if id(eqn) not in compiled:
            compiled.add(id(eqn))
            time.sleep(COMPILE_S)
        return bind(eqn, invals)

    monkeypatch.setattr(executor_mod, "_eval_eqn", eval_eqn)
    ctl = GlobalController(profile=PROFILE, async_swap=False)
    h = ctl.submit(JobSpec("j", iterations=3, payload=_payload(5)))
    ctl.wait(timeout=300)
    assert h.error is None
    assert h.stats[0].wall_time_s > len(compiled) * COMPILE_S
    assert h.stats[1].replans == 0
    # folded, a 20-ms sample would leave at least 8.8 ms on every op of
    # the sequence after three iterations, and 9.8 ms in the hub
    measured = ctl.telemetry.op_latencies("j")
    assert len(measured) == len(h.seq.operators)
    assert sum(measured.values()) / len(measured) < COMPILE_S / 5
    lat = [op.latency for op in h.seq.operators]
    assert sum(lat) / len(lat) < COMPILE_S / 5
