"""Cross-job arbitration tests: BudgetArbiter splits (equal / priority /
peak-proportional, demand-capped water-filling), planning against
arbiter-assigned per-job budgets, device-budget certification in the
shared DeviceLedger, budget reclaim on job finish, and loud surfacing of
job-thread failures."""
import pytest

from repro.core import (ARBITER_POLICIES, BudgetArbiter, GlobalController,
                        JaxprExecutor, JobFailedError, MachineProfile,
                        MemoryEngine, SchedulerConfig, analyze,
                        build_pipeline, simulate)

from repro.service import JobSpec

from helpers import capture_mlp, mlp_train_step, synthetic_chain

PROFILE = MachineProfile(host_link_bw=16e9, compute_flops=5e10, mem_bw=1e10)


@pytest.fixture(scope="module")
def two_mlps():
    a, _, _ = capture_mlp(sizes=(64, 128, 128, 8), batch=16, job_id="a")
    b, _, _ = capture_mlp(sizes=(64, 128, 128, 8), batch=16, job_id="b")
    return a, b


# ---------------------------------------------------------------- arbiter
def test_split_policies_and_registry():
    assert {"equal", "priority", "peak"} <= set(ARBITER_POLICIES)
    with pytest.raises(KeyError):
        BudgetArbiter(100, policy="no-such-policy")

    arb = BudgetArbiter(1000, policy="equal")
    arb.register("a")
    arb.register("b")
    assert arb.split(["a", "b"]) == {"a": 500, "b": 500}

    arb = BudgetArbiter(1000, policy="priority")
    arb.register("hi", priority=3.0)
    arb.register("lo", priority=1.0)
    split = arb.split(["hi", "lo"])
    assert split["hi"] == 750 and split["lo"] == 250

    arb = BudgetArbiter(1000, policy="peak")
    arb.register("big", demand_bytes=600)
    arb.register("small", demand_bytes=200)
    split = arb.split(["big", "small"])
    assert split["big"] == 3 * split["small"]
    assert sum(split.values()) <= 1000


def test_split_caps_at_demand_and_redistributes():
    """Water-filling: a job that cannot use its share is capped at its
    demand; the surplus re-flows to the uncapped jobs."""
    arb = BudgetArbiter(1000, policy="equal")
    arb.register("tiny", demand_bytes=100)
    arb.register("hungry", demand_bytes=0)    # unknown demand: uncapped
    split = arb.split(["tiny", "hungry"])
    assert split["tiny"] == 100
    assert split["hungry"] == 900
    assert sum(split.values()) <= 1000


def test_finishing_job_bytes_reclaimed_and_redistributed():
    """On every finish the controller re-splits; the survivor's next plan
    gets the departed job's bytes back."""
    arb = BudgetArbiter(1 << 20, policy="equal")
    arb.register("long")
    arb.register("short")
    first = arb.split(["long", "short"])
    arb.unregister("short")
    second = arb.split(["long"])
    assert second["long"] > first["long"]
    assert second["long"] == 1 << 20
    assert arb.history == [first, second]


# ------------------------------------------------- budget-aware planning
def test_two_staggered_jobs_respect_device_budget_in_shared_ledger(two_mlps):
    """The arbiter splits the device budget, each job plans against its
    slice, and the *simulated execution* on one capacity-limited shared
    DeviceLedger never exceeds the device budget (zero OOM events) —
    while the vanilla run of the same two jobs busts it."""
    a, b = two_mlps
    offsets = {"a": 0.0, "b": 0.5 * a.iteration_time}
    vanilla = simulate([a, b], None, PROFILE, iterations=2, offsets=offsets,
                       free_at_last_use=False)
    budget = int(vanilla.peak_bytes * 0.5)
    assert vanilla.peak_bytes > budget     # vanilla exceeds the budget

    arb = BudgetArbiter(budget, policy="equal")
    for s in (a, b):
        arb.register(s.job_id,
                     demand_bytes=analyze(
                         [s], free_at_last_use=False).peak_bytes)
    budgets = arb.split(["a", "b"])
    assert sum(budgets.values()) <= budget

    cfg = SchedulerConfig(memory_budget_bytes=budget,
                          per_job_budget_bytes=budgets)
    res = build_pipeline("tensile+autoscale", profile=PROFILE,
                         config=cfg).plan([a, b], offsets=offsets)
    assert res.plans["a"].budget_bytes == budgets["a"]

    eng = MemoryEngine(PROFILE, capacity_bytes=budget)
    sim = simulate([a, b], res.plans, PROFILE, iterations=2,
                   offsets=offsets, engine=eng)
    assert eng.ledger.peak <= budget
    assert eng.ledger.oom_events == 0
    assert sim.peak_bytes == eng.ledger.peak   # one shared ledger


def test_high_priority_job_keeps_weighted_share(two_mlps):
    """Under tensile+priority the high-priority job's plan retains at
    least its weighted share: swap victims come from the low-priority job
    first, so hi's planned residency dominates lo's."""
    a, b = two_mlps          # identical shapes -> differences are policy
    prios = {"a": 3.0, "b": 1.0}
    offsets = {"a": 0.0, "b": 0.25 * a.iteration_time}
    van = analyze([a, b], offsets=offsets, free_at_last_use=False).peak_bytes
    budget = int(van * 0.5)
    arb = BudgetArbiter(budget, policy="priority")
    arb.register("a", priority=3.0)
    arb.register("b", priority=1.0)
    budgets = arb.split(["a", "b"])
    # weighted 3:1 shares (independent floor-division: tolerance of a few
    # bytes, not exact equality)
    assert abs(budgets["a"] - 3 * budgets["b"]) <= 3

    cfg = SchedulerConfig(memory_budget_bytes=budget,
                          per_job_budget_bytes=budgets,
                          job_priorities=prios)
    res = build_pipeline("tensile+priority", profile=PROFILE,
                         config=cfg).plan([a, b], offsets=offsets)
    peaks = res.final_report.per_job_peak
    assert peaks["a"] >= peaks["b"]
    # hi keeps >= its weight share of what planning left resident
    assert peaks["a"] / max(peaks["a"] + peaks["b"], 1) >= 0.5


def test_autoscale_pass_enforces_tight_per_job_budget():
    """BudgetAutoscalePass acts when plain greedy swapping leaves a job
    over its arbiter slice: per-job budgets tighter than what global
    largest-first reaches force job-targeted steps."""
    a = synthetic_chain(n_ops=10, latency=2.0, job_id="a", seed=1)
    b = synthetic_chain(n_ops=10, latency=2.0, job_id="b", seed=2)
    prof = MachineProfile(host_link_bw=1e6, host_link_latency=1e-3,
                          compute_flops=1e9, mem_bw=1e9)
    solo = {j: analyze([s]).peak_bytes for j, s in (("a", a), ("b", b))}
    budgets = {j: int(p * 0.55) for j, p in solo.items()}
    cfg = SchedulerConfig(per_job_budget_bytes=budgets)
    res = build_pipeline("tensile+autoscale", profile=prof,
                         config=cfg).plan([a, b], offsets={"b": 3.0})
    after = res.final_report.per_job_peak
    # every job moved toward its slice vs its unscheduled solo peak
    for j in ("a", "b"):
        assert after[j] < solo[j]
    assert res.pass_steps["swap"] > 0


# ------------------------------------------------ controller integration
def _make_job(j):
    import jax

    from repro.optim.adam import adamw_init

    from helpers import mlp_params
    p = mlp_params(jax.random.PRNGKey(j), [32, 64, 64, 4])
    o = adamw_init(p)
    b = (jax.random.normal(jax.random.PRNGKey(10 + j), (8, 32)),
         jax.random.normal(jax.random.PRNGKey(20 + j), (8, 4)))
    return p, o, b


def test_controller_arbitrated_staggered_jobs():
    """End-to-end: two staggered jobs under the arbitrated controller —
    budgets re-split at launch AND at finish, per-job ledger views carry
    the slices, plans swap at iteration boundaries, nothing fails."""
    gc = GlobalController(profile=PROFILE, async_swap=False,
                          pipeline_name="tensile+autoscale",
                          arbiter_policy="equal")
    p, o, b = _make_job(0)
    gc.submit(JobSpec("j0", iterations=2,
                      payload=(mlp_train_step, p, o, b)))
    p, o, b = _make_job(1)
    gc.submit(JobSpec("j1", iterations=2, priority=2.0,
                      payload=(mlp_train_step, p, o, b)))
    gc.wait(timeout=300)
    assert all(h.done and h.error is None for h in gc.jobs.values())
    # the launch of j1 re-split over {j0, j1}; each finish re-split again
    assert gc.arbiter is not None and len(gc.arbiter.history) >= 2
    assert any(set(s) == {"j0", "j1"} for s in gc.arbiter.history)
    for split in gc.arbiter.history:
        assert sum(split.values()) <= gc.arbiter.capacity
    for h in gc.jobs.values():
        assert h.ledger_view is not None
        assert h.ledger_view.budget_bytes is not None
        assert h.ledger_view.peak == gc.accountant.job_peak(h.job_id)
    assert gc.global_peak_bytes > 0
    assert gc.replan_count >= 3     # 2 launches + >=1 finish re-split


def test_departure_with_zero_reclaimed_bytes_skips_replan(two_mlps):
    """Regression: a finished job that held ZERO bytes of the arbiter
    split (an under-demand job) reclaims nothing — its departure must NOT
    trigger a survivors' replan (it would rebuild identical plans), while
    a departure that does reclaim bytes still re-splits."""
    from repro.core import JobHandle

    a, b = two_mlps
    c = a.clone("c")
    gc = GlobalController(profile=PROFILE, async_swap=False,
                          pipeline_name="tensile+autoscale",
                          arbiter_policy="equal")
    for s in (a, b, c):
        gc.scheduler.register_job(s)
        gc.jobs[s.job_id] = JobHandle(job_id=s.job_id, seq=s,
                                      closed_jaxpr=None, args=(),
                                      iterations=1)
        gc.arbiter.register(s.job_id)
    gc.arbiter.split(["a", "b", "c"])
    # job "a" finished holding none of the split (demand-capped to zero)
    gc.arbiter.last_assignment["a"] = 0

    before = gc.replan_count
    gc._on_job_exit(gc.jobs["a"])
    assert gc.replan_count == before          # no-op replan skipped
    assert "a" not in gc.arbiter.priorities   # still deregistered
    assert "a" not in gc.scheduler.jobs

    # a departure that DOES reclaim bytes replans the survivors
    assert gc.arbiter.last_assignment["b"] > 0
    gc._on_job_exit(gc.jobs["b"])
    assert gc.replan_count == before + 1
    assert gc.jobs["c"].plan is not None      # survivor got a fresh plan


def test_job_thread_failure_surfaces_loudly(monkeypatch):
    """A job thread dying must not be silent: wait() raises JobFailedError
    naming the job, chaining the original exception, and carrying the
    thread's traceback."""
    def boom(self, *args, **kwargs):
        raise RuntimeError("executor exploded")

    monkeypatch.setattr(JaxprExecutor, "run_flat", boom)
    gc = GlobalController(profile=PROFILE, async_swap=False)
    p, o, b = _make_job(0)
    gc.submit(JobSpec("doomed", iterations=1,
                      payload=(mlp_train_step, p, o, b)))
    with pytest.raises(JobFailedError) as ei:
        gc.wait(timeout=120)
    err = ei.value
    assert "doomed" in str(err)
    assert "executor exploded" in str(err)
    assert isinstance(err.failures["doomed"], RuntimeError)
    assert isinstance(err.__cause__, RuntimeError)
    assert "RuntimeError" in err.tracebacks["doomed"]
    assert gc.failures() and gc.jobs["doomed"].error_tb
    # non-raising inspection path still reports
    gc.wait(timeout=1, raise_errors=False)
