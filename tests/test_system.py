"""End-to-end behaviour tests for the TENSILE system (paper pipeline:
capture → schedule → execute → update)."""
import numpy as np
import pytest

import jax

from repro.core import (GlobalController, JaxprExecutor, MachineProfile,
                        MemoryScheduler, SchedulerConfig, evaluate,
                        reference_outputs, schedule_single)

from repro.service import JobSpec

from helpers import capture_mlp, mlp_train_step

PROFILE = MachineProfile(host_link_bw=16e9, compute_flops=5e10, mem_bw=1e10)


@pytest.fixture(scope="module")
def mlp():
    return capture_mlp(sizes=(64, 256, 256, 256, 8), batch=32)


def test_capture_classifies_tensors(mlp):
    seq, closed, _ = mlp
    kinds = {t.kind.value for t in seq.tensors.values()}
    assert {"param", "opt_state", "activation", "input"} <= kinds
    aliased = [t for t in seq.tensors.values() if t.updates]
    # 3 layers × (w, b) × (param + 2 moments) aliases minimum
    assert len(aliased) >= 8


def test_schedule_reduces_peak(mlp):
    seq, _, _ = mlp
    res = schedule_single(seq, profile=PROFILE)
    assert res.swaps_scheduled > 0
    assert res.memory_saving_ratio > 0.2
    assert any(e.crosses_iteration for e in res.plans[seq.job_id].events)


def test_executor_matches_reference_under_plan(mlp):
    seq, closed, args = mlp
    res = schedule_single(seq, profile=PROFILE)
    ref = reference_outputs(closed, *args)
    ex = JaxprExecutor(closed, seq, res.plans[seq.job_id])
    out = ex.run(*args)
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert ex.stats.swap_out_count > 0
    ex.close()


def test_executor_peak_below_vanilla(mlp):
    seq, closed, args = mlp
    res = schedule_single(seq, profile=PROFILE)
    ex0 = JaxprExecutor(closed, seq, None)
    ex0.run(*args)
    ex1 = JaxprExecutor(closed, seq, res.plans[seq.job_id])
    ex1.run(*args)
    assert ex1.stats.peak_bytes < ex0.stats.peak_bytes
    ex0.close(), ex1.close()


def test_simulated_metrics(mlp):
    seq, _, _ = mlp
    res = schedule_single(seq, profile=PROFILE)
    m = evaluate([seq], res.plans, PROFILE)
    assert 0.0 < m["MSR"] <= 1.0
    assert m["EOR"] < 1.0  # swaps mostly overlap compute
    assert m["CBR"] > 1.0


def test_plan_update_on_drift(mlp):
    seq, _, _ = mlp
    sched = MemoryScheduler(PROFILE, SchedulerConfig(update_threshold=0.2))
    sched.register_job(seq)
    sched.schedule()
    small = [op.latency * 1.01 for op in seq.operators]
    assert not sched.update_latencies(seq.job_id, small)
    big = [op.latency * 5.0 for op in seq.operators]
    assert sched.update_latencies(seq.job_id, big)
    res2 = sched.schedule()
    assert res2.plans[seq.job_id].events  # replanning still yields a plan


def test_global_controller_multi_job():
    import jax

    from repro.optim.adam import adamw_init

    def make_job(j):
        from helpers import mlp_params
        p = mlp_params(jax.random.PRNGKey(j), [32, 64, 64, 4])
        o = adamw_init(p)
        b = (jax.random.normal(jax.random.PRNGKey(10 + j), (8, 32)),
             jax.random.normal(jax.random.PRNGKey(20 + j), (8, 4)))
        return p, o, b

    gc = GlobalController(profile=PROFILE, async_swap=True)
    for j in range(2):
        p, o, b = make_job(j)
        gc.submit(JobSpec(f"j{j}", iterations=2,
                          payload=(mlp_train_step, p, o, b)))
    gc.wait(timeout=180)
    assert all(h.done and h.error is None for h in gc.jobs.values())
    assert gc.global_peak_bytes > 0
    assert gc.replan_count >= 1


def test_controller_job_frees_its_input_state_during_a_step(monkeypatch):
    """The controller hands a job's state to the executor and keeps no
    reference of its own, so the live bytes of an unscheduled step never
    exceed the step's free-at-last-use peak: the old params and moments
    are freed as the update replaces them."""
    import gc as pygc

    import repro.core.executor as executor_mod
    from helpers import mlp_params
    from repro.core.peak_analysis import analyze
    from repro.optim.adam import adamw_init

    def live_bytes():
        return sum(a.nbytes for a in jax.live_arrays())

    peaks = []
    bind = executor_mod._eval_eqn

    def eval_and_measure(eqn, invals):
        outs = bind(eqn, invals)
        peaks.append(live_bytes())
        return outs

    monkeypatch.setattr(executor_mod, "_eval_eqn", eval_and_measure)
    pygc.collect()
    baseline = live_bytes()

    def payload():
        p = mlp_params(jax.random.PRNGKey(0), [32, 256, 256, 4])
        b = (jax.random.normal(jax.random.PRNGKey(1), (8, 32)),
             jax.random.normal(jax.random.PRNGKey(2), (8, 4)))
        return mlp_train_step, p, adamw_init(p), b

    ctl = GlobalController(profile=PROFILE, pipeline_name="vanilla")
    handle = ctl.submit(JobSpec("own", iterations=3, payload=payload()))
    ctl.wait(timeout=180)
    assert handle.error is None
    step_peak = analyze([handle.seq]).peak_bytes
    state = sum(a.nbytes for a in jax.tree.leaves(handle.args[:2]))
    assert state > 0.1 * step_peak  # pinning the old state would show
    assert max(peaks) - baseline <= step_peak * 1.01


def test_scheduled_losses_match_vanilla_with_slow_swap_outs(monkeypatch):
    """Swap-outs still on the wire when the optimizer updates their
    storage in place (a slow host link) must not cost the new value: the
    scheduled run's losses stay bit-identical to the unscheduled run's."""
    import time

    from helpers import mlp_params
    from repro.core.executor import AsyncSwapExecutor
    from repro.core.peak_analysis import analyze
    from repro.optim.adam import adamw_init

    submit = AsyncSwapExecutor.submit

    def slow_out(self, key, fn):
        if key.startswith("out:"):
            def late(fn=fn):
                time.sleep(0.01)
                fn()
            return submit(self, key, late)
        return submit(self, key, fn)

    monkeypatch.setattr(AsyncSwapExecutor, "submit", slow_out)

    def payload():
        p = mlp_params(jax.random.PRNGKey(0), [32, 128, 128, 128, 4])
        b = (jax.random.normal(jax.random.PRNGKey(1), (8, 32)),
             jax.random.normal(jax.random.PRNGKey(2), (8, 4)))
        return mlp_train_step, p, adamw_init(p), b

    def run(pipeline, budget=None):
        ctl = GlobalController(
            profile=PROFILE, pipeline_name=pipeline,
            scheduler_config=SchedulerConfig(memory_budget_bytes=budget))
        h = ctl.submit(JobSpec(pipeline, iterations=3, payload=payload()))
        ctl.wait(timeout=180)
        assert h.error is None
        return [np.asarray(out[0]) for out in h.outputs], h

    ref, h = run("vanilla")
    got, h2 = run("tensile", int(0.6 * analyze([h.seq]).peak_bytes))
    assert sum(s.swap_out_count for s in h2.stats) > 0
    assert [x.tobytes() for x in got] == [x.tobytes() for x in ref]
