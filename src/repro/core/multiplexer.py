"""Global Controller (paper §III-D, Fig. 3) + cross-job budget arbitration.

Owns the job registry for a device: launches each job's Executor on its own
thread, funnels measured operator latencies back to the Memory Scheduler,
triggers re-planning when latencies drift past the update threshold
(§IV-E), and distributes fresh plans — applied by each Executor at its next
iteration boundary, exactly as the paper specifies ("the system will apply
the new plan right before computing the next batch of data").

The four-step scheduling procedure of §III-D maps to:
  1. `launch()`      — collect the new job's graph + cold-start latencies
                       (CostModel / LatencyMLP prediction, no passive mode)
  2. `_replan()`     — Memory Scheduler generates/updates the plans
  3. Executor threads + the shared AsyncSwapExecutor run the plans
  4. latency reports — EWMA-folded; drift beyond threshold triggers 2.

Beyond the paper: the **BudgetArbiter** owns the device-wide byte budget
and splits it across live jobs by a pluggable policy (equal-share,
priority-weighted, peak-proportional from measured per-job peaks).  The
split is recomputed at every launch, every finish (the departing job's
bytes are reclaimed and redistributed — skipped when the departing job
held zero bytes of the split), and every latency-drift replan; per-job
pipelines then plan against the arbiter-assigned slice instead of the
full device (passes.PriorityPass / passes.BudgetAutoscalePass).

Plan versions swap at iteration boundaries by default, so a budget move
never tears an in-flight iteration.  In arbiter mode ``"preempt"`` a
SHRUNKEN slice additionally takes effect mid-iteration: the controller
builds an incremental remainder plan (``MemoryScheduler.replan_from``)
and hot-swaps it into the victim's running executor at its next *safe
point* (``engine.find_safe_points`` — no transfer in flight, residency
at a local minimum), closing the across-iteration lag a bursty arrival
otherwise suffers.  See docs/architecture.md, "Safe points and plan
hot-swap".
"""
from __future__ import annotations

import dataclasses
import threading
import time as _time
import traceback
import warnings
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

from ..obs.spans import gc_spans, span
from .access import AccessSequence
from .cost_model import CostModel, EWMATracker
from .engine import (DeviceLedger, DmaChannel, JobLedgerView, MemoryEngine,
                     find_safe_points)
from .executor import JaxprExecutor
from .experience import ExperienceStore, device_identity
from .graph_capture import capture_train_step
from .peak_analysis import analyze
from .plan import MachineProfile, SchedulingPlan
from .scheduler import MemoryScheduler, SchedulerConfig
from .telemetry import TelemetryHub


class JobFailedError(RuntimeError):
    """One or more job threads died.  Carries every failed handle so a
    multi-job failure is reported whole instead of masking all but the
    first; the first underlying exception is chained as ``__cause__``."""

    def __init__(self, failures: Dict[str, BaseException],
                 tracebacks: Optional[Dict[str, str]] = None):
        self.failures = dict(failures)
        self.tracebacks = dict(tracebacks or {})
        detail = "; ".join(
            f"{j}: {type(e).__name__}: {e}" for j, e in self.failures.items())
        super().__init__(
            f"{len(self.failures)} job(s) failed — {detail}")


@dataclasses.dataclass
class JobHandle:
    job_id: str
    seq: AccessSequence
    closed_jaxpr: Any
    # a train job's (params, opt_state, batch): the initial state until
    # it runs, None while its executor owns the state, the final state
    # after a clean finish
    args: Optional[tuple]
    iterations: int
    priority: float = 1.0
    thread: Optional[threading.Thread] = None
    plan: Optional[SchedulingPlan] = None
    plan_version: int = 0
    done: bool = False
    error: Optional[BaseException] = None
    error_tb: Optional[str] = None
    stats: List[Any] = dataclasses.field(default_factory=list)
    step_times: List[float] = dataclasses.field(default_factory=list)
    peak_bytes: int = 0
    # the arbiter-assigned slice of the device budget, as a live view over
    # the shared DeviceLedger (None until the first split)
    ledger_view: Optional[JobLedgerView] = None
    # structural fingerprint in the attached ExperienceStore (None when
    # the controller runs without one)
    fingerprint: Optional[str] = None
    # the executor currently running this job's iteration (None between
    # iterations / after finish) — the preemptive arbiter hot-swaps plans
    # into it at a safe point
    executor: Optional[Any] = None
    # (plan_version, safe_op) of every preemptive hot-swap requested
    preemptions: List[Any] = dataclasses.field(default_factory=list)
    # the JobSpec this handle was submitted with (None only for handles
    # built outside the submit() path)
    spec: Optional[Any] = None
    # admission-time predicted peak (captured only when a DriftMonitor is
    # attached — the measured peak is compared against it on exit)
    predicted_peak: Optional[int] = None
    # per iteration, the step's outputs after the new params and
    # optimizer state (a train step's loss)
    outputs: List[Any] = dataclasses.field(default_factory=list)
    # indices of the equations the job's executors have bound: each
    # executor's first bind of one not in it is cold (it compiles)
    bound_eqns: Set[int] = dataclasses.field(default_factory=set)

    @property
    def budget_bytes(self) -> Optional[int]:
        return self.ledger_view.budget_bytes if self.ledger_view else None


def _is_serve(handle: "JobHandle") -> bool:
    """Serve handles are discriminated by their spec's ``kind``, NOT by
    ``closed_jaxpr is None`` — handles built outside submit() (tests,
    manual registration) legitimately carry no jaxpr but are training
    jobs as far as the iteration-DAG scheduler is concerned."""
    return (handle.spec is not None
            and getattr(handle.spec, "kind", "train") == "serve")


@dataclasses.dataclass
class CapturedJob:
    """A JobSpec resolved and captured: everything admission + submit need.

    Produced by ``GlobalController.capture_spec`` so the service daemon can
    predict a job's peak (``predict_peak``) *before* committing to
    ``submit`` — capture once, admit, then run from the same capture."""

    seq: AccessSequence
    closed_jaxpr: Any
    args: Tuple[Any, Any, Any]
    fingerprint: Optional[str] = None


# ----------------------------------------------------------------------
# Budget arbitration (device-wide budget -> per-job slices)
# ----------------------------------------------------------------------
def _equal_weights(arb: "BudgetArbiter", live: Sequence[str]
                   ) -> Dict[str, float]:
    return {j: 1.0 for j in live}


def _priority_weights(arb: "BudgetArbiter", live: Sequence[str]
                      ) -> Dict[str, float]:
    return {j: max(arb.priorities.get(j, 1.0), 1e-9) for j in live}


def _peak_weights(arb: "BudgetArbiter", live: Sequence[str]
                  ) -> Dict[str, float]:
    """Proportional to each job's peak demand: the measured per-job peak
    (folded in from the shared DeviceLedger / EngineTrace as the job runs)
    once available, else a persisted peak a PRIOR run measured for the
    same fingerprint (experience prior), else the predicted vanilla peak
    from capture."""
    out: Dict[str, float] = {}
    for j in live:
        w = arb.demands.get(j, 0)
        prior = arb.priors.get(j)
        if prior is not None and prior.peak_bytes \
                and j not in arb.live_peak_seen:
            w = prior.peak_bytes
        out[j] = float(max(w, 1))
    return out


# how strongly a job's measured stall share bids for extra bytes under
# the eor-learned policy: weight = 1 + GAIN * stall_share (stall_share in
# [0, 1], so weights stay within [1, 1+GAIN] — bounded re-splits)
EOR_LEARNED_GAIN = 3.0


def _eor_learned_weights(arb: "BudgetArbiter", live: Sequence[str]
                         ) -> Dict[str, float]:
    """Learned from the measured-telemetry plane: a job losing more of
    its measured time to memory stalls (passive swap-ins, late
    prefetches) is the job whose slice is too small — it bids for more
    bytes in proportion to its measured stall share.  Jobs with no live
    samples yet bid the stall share a PRIOR run persisted for the same
    fingerprint (experience prior) when one exists, else the neutral
    weight — so the policy degrades to equal-share only on a genuinely
    first-ever run."""
    hub = arb.telemetry
    out: Dict[str, float] = {}
    for j in live:
        share = None
        if hub is not None and hub.has_samples(j):
            share = hub.stall_share(j)
        if share is None:
            prior = arb.priors.get(j)
            share = prior.stall_share if prior is not None else 0.0
        out[j] = 1.0 + EOR_LEARNED_GAIN * share
    return out


ARBITER_POLICIES: Dict[str, Callable[["BudgetArbiter", Sequence[str]],
                                     Dict[str, float]]] = {
    "equal": _equal_weights,
    "priority": _priority_weights,
    "peak": _peak_weights,
    "eor-learned": _eor_learned_weights,
}


ARBITER_MODES = ("boundary", "preempt")


class BudgetArbiter:
    """Owns the device-wide byte budget and splits it across live jobs.

    ``split(live)`` runs weighted water-filling: each job's raw share is
    ``capacity * w_j / Σw``; a job whose known demand (its vanilla peak —
    it can never profitably hold more) is below its share is capped at the
    demand and the surplus re-flows to the uncapped jobs.  Policies are
    pluggable via ``ARBITER_POLICIES`` (equal / priority / peak).  Every
    split is appended to ``history`` so tests and reports can audit how
    budgets moved across launch/finish/drift replans.

    ``mode`` decides how a *shrunken* slice takes effect on a running job:
    ``"boundary"`` (default, the paper's rule) waits for the victim's next
    iteration boundary; ``"preempt"`` additionally hot-swaps an incremental
    remainder plan in at the victim's next safe point, shrinking it
    mid-iteration (``GlobalController._preempt_victims``).
    """

    def __init__(self, capacity_bytes: int, policy: str = "equal",
                 mode: str = "boundary",
                 telemetry: Optional[TelemetryHub] = None):
        if policy not in ARBITER_POLICIES:
            raise KeyError(f"unknown arbiter policy {policy!r}; "
                           f"known: {sorted(ARBITER_POLICIES)}")
        if mode not in ARBITER_MODES:
            raise KeyError(f"unknown arbiter mode {mode!r}; "
                           f"known: {list(ARBITER_MODES)}")
        self.capacity = int(capacity_bytes)
        self.policy = policy
        self.mode = mode
        # measured-telemetry plane: the eor-learned policy reads each
        # job's measured stall share from here (None -> equal weights)
        self.telemetry = telemetry
        self.priorities: Dict[str, float] = {}
        self.demands: Dict[str, int] = {}       # peak demand, bytes
        # experience priors: persisted telemetry summaries standing in
        # for live measurements on jobs that have not produced any yet
        # (set_prior; consumed by the eor-learned and peak policies)
        self.priors: Dict[str, Any] = {}
        # jobs whose demand has been updated from a LIVE measured peak —
        # from then on the prior stops overriding the peak policy
        self.live_peak_seen: Dict[str, bool] = {}
        self.history: List[Dict[str, int]] = []
        self.last_assignment: Dict[str, int] = {}

    # -- victim selection ----------------------------------------------
    def victims(self, new_assignment: Dict[str, int],
                prev_assignment: Dict[str, int],
                usage: Dict[str, int]) -> List[str]:
        """Jobs whose slice shrank under the new split and whose usage
        exceeds the new slice — the jobs preemption must act on, largest
        over-share first.  ``usage`` should be the job's *expected*
        footprint under its running plan (the controller passes
        max(live bytes, measured peak)): a victim below its new slice at
        the split instant but heading over it later in the iteration
        still needs the mid-iteration shrink."""
        out = [j for j, b in new_assignment.items()
               if j in prev_assignment and b < prev_assignment[j]
               and usage.get(j, 0) > b]
        out.sort(key=lambda j: new_assignment[j] - usage.get(j, 0))
        return out

    # -- registry ------------------------------------------------------
    def register(self, job_id: str, priority: float = 1.0,
                 demand_bytes: int = 0) -> None:
        self.priorities[job_id] = priority
        self.demands[job_id] = int(demand_bytes)

    def update_demand(self, job_id: str, demand_bytes: int) -> None:
        """Fold in a measured peak (monotone max — demand never shrinks
        within a job's lifetime)."""
        if job_id in self.demands:
            self.demands[job_id] = max(self.demands[job_id],
                                       int(demand_bytes))
            self.live_peak_seen[job_id] = True

    def set_prior(self, job_id: str, prior) -> None:
        """Attach a persisted experience prior (a TelemetrySummary-shaped
        object with ``stall_share`` and ``peak_bytes``) for a job that
        has not produced live samples yet — the eor-learned and peak
        policies read it until live telemetry supersedes it."""
        if prior is not None:
            self.priors[job_id] = prior

    def unregister(self, job_id: str) -> None:
        self.priorities.pop(job_id, None)
        self.demands.pop(job_id, None)
        self.priors.pop(job_id, None)
        self.live_peak_seen.pop(job_id, None)

    # -- the split -----------------------------------------------------
    def split(self, live: Sequence[str]) -> Dict[str, int]:
        live = [j for j in live if j in self.priorities]
        if not live:
            self.last_assignment = {}
            return {}
        weights = ARBITER_POLICIES[self.policy](self, live)
        assignment: Dict[str, int] = {}
        remaining = self.capacity
        pool = sorted(live)
        # water-fill: repeatedly give each job its weighted share of what
        # is left; jobs capped by demand leave the pool and their surplus
        # re-flows (bounded by len(live) rounds)
        while pool and remaining > 0:
            total_w = sum(weights[j] for j in pool)
            capped = []
            for j in pool:
                share = int(remaining * weights[j] / total_w)
                demand = self.demands.get(j, 0)
                if demand and demand < share:
                    assignment[j] = demand
                    capped.append(j)
            if not capped:
                for j in pool:
                    assignment[j] = int(remaining * weights[j] / total_w)
                break
            remaining -= sum(assignment[j] for j in capped)
            pool = [j for j in pool if j not in capped]
        for j in live:
            assignment.setdefault(j, 0)
        self.last_assignment = dict(assignment)
        self.history.append(dict(assignment))
        return assignment


class GlobalController:
    def __init__(self, profile: Optional[MachineProfile] = None,
                 scheduler_config: Optional[SchedulerConfig] = None,
                 cost_model: Optional[CostModel] = None,
                 device_capacity: Optional[int] = None,
                 async_swap: bool = True,
                 pipeline_name: Optional[str] = None,
                 arbiter: Optional[BudgetArbiter] = None,
                 arbiter_policy: Optional[str] = None,
                 arbiter_mode: Optional[str] = None,
                 telemetry: Optional[TelemetryHub] = None,
                 safe_point_source: str = "measured",
                 experience: Optional[ExperienceStore] = None,
                 experience_dir: Optional[str] = None,
                 events=None, drift=None):
        self.profile = profile or MachineProfile()
        # structured event stream (observability plane): failure paths
        # that must never take a job down with them — experience
        # flushes, survivor replans, preempt replans — emit WARN events
        # here IN ADDITION to their recoverable-failure lists, so a
        # silent list append becomes a visible, timestamped signal.
        # Always present (a bounded ring buffer costs nothing idle).
        if events is None:
            from ..obs.events import EventLog
            events = EventLog()
        self.events = events
        # optional sim-vs-measured drift monitor: when attached, submit
        # captures the predicted peak and _on_job_exit feeds it the
        # measured one.  None (the default) adds zero work per job.
        self.drift = drift
        # ONE measured-telemetry hub per device: every executor produces
        # into it; safe-point detection, drift replans, swap-window sizing
        # and the eor-learned arbiter policy consume from it
        self.telemetry = telemetry or TelemetryHub(clock="real")
        # the experience plane (cross-run persistence): an attached store
        # warm-boots the cost model's calibration, the pipeline's plan
        # cache, the planner's DMA bandwidth, and the arbiter's learned
        # priors — and distilled experience flushes back on job finish
        if experience is None and experience_dir is not None:
            experience = ExperienceStore(
                experience_dir, device_id=device_identity(self.profile))
        self.experience = experience
        # (job_id, error) for experience flushes that failed — persistence
        # must never take a job down with it
        self.experience_failures: List[tuple] = []
        # how `_preempt_victims` finds splice points: "measured" detects
        # them from the hub's residency records (falling back to modeled
        # below min_iterations of samples — §IV-C blending), "modeled"
        # always uses the plan's DeviceLedger model
        self.safe_point_source = safe_point_source
        pipeline = None
        if pipeline_name is not None:
            from .passes import build_pipeline
            cfg = scheduler_config or SchedulerConfig()
            scheduler_config = cfg
            pipeline = build_pipeline(pipeline_name, profile=self.profile,
                                      config=cfg)
        self.scheduler = MemoryScheduler(self.profile, scheduler_config,
                                         pipeline=pipeline,
                                         experience=self.experience)
        if self.scheduler.pipeline.telemetry is None:
            self.scheduler.pipeline.telemetry = self.telemetry
        # cost model warm boot: with a store attached, capture-time
        # latency estimates start from the calibration a prior run
        # persisted instead of probe constants (and keep recalibrating
        # online from the hub — see report_telemetry)
        self.cost_model = cost_model or CostModel(experience=self.experience)
        # one engine ledger + DMA channel shared by every job on the device
        self.engine = MemoryEngine(self.profile,
                                   capacity_bytes=device_capacity,
                                   telemetry=self.telemetry)
        self.accountant: DeviceLedger = self.engine.ledger
        self.channel: DmaChannel = self.engine.channel
        # the device-wide budget the arbiter splits: explicit capacity,
        # else the scheduler's budget, else the device size
        cap = device_capacity
        if cap is None:
            cap = (self.scheduler.config.memory_budget_bytes
                   or self.profile.device_memory_bytes)
        mode = arbiter_mode or self.scheduler.config.arbiter_mode
        self.arbiter = arbiter or (
            BudgetArbiter(cap, policy=arbiter_policy, mode=mode,
                          telemetry=self.telemetry)
            if arbiter_policy is not None else None)
        if self.arbiter is not None and self.arbiter.telemetry is None:
            self.arbiter.telemetry = self.telemetry
        self.async_swap = async_swap
        self.jobs: Dict[str, JobHandle] = {}
        self.ewma: Dict[str, EWMATracker] = {}
        self._lock = threading.Lock()
        self._replan_count = 0
        self._preempt_count = 0
        # replans that failed while redistributing a departed job's budget
        # (survivors keep their current plans): (departed_job_id, error)
        self.replan_failures: List[tuple] = []
        # incremental replans that failed while preempting a victim (the
        # victim keeps its plan until the boundary): (job_id, error)
        self.preempt_failures: List[tuple] = []

    # ------------------------------------------------------------------
    def capture_spec(self, spec) -> CapturedJob:
        """Admission hook #1: resolve a ``JobSpec`` and capture its graph.

        Resolution goes through ``repro.service.workloads`` (in-process
        ``spec.payload`` wins; otherwise the registered / importable
        workload factory named by ``spec.workload``).  The capture is
        reusable: the daemon captures once, predicts the peak, and hands
        the same ``CapturedJob`` to ``submit`` after admission.

        Serve specs (``kind="serve"``) have no jaxpr to capture — their
        timeline is request-driven, not an iteration DAG.  They capture to
        a *synthetic* access sequence whose tensors are the per-slot KV
        footprints, so ``predict_peak`` and the arbiter's demand math see
        a serving job through the same lens as a training one."""
        if getattr(spec, "kind", "train") == "serve":
            return self._capture_serve_spec(spec)
        from ..service.workloads import resolve_workload
        step_fn, params, opt_state, batch = resolve_workload(spec)
        # reflect current device contention into cold-start predictions
        self.cost_model.utilization = min(
            0.9, 0.3 * sum(1 for j in self.jobs.values() if not j.done))
        seq, closed = capture_train_step(
            step_fn, params, opt_state, batch, job_id=spec.job_id,
            cost_model=self.cost_model)
        fp = spec.fingerprint
        if self.experience is not None:
            try:
                fp = self.experience.fingerprint(seq)
            except Exception as e:  # noqa: BLE001 - cold boot instead
                self.experience_failures.append((spec.job_id, e))
                self.events.warn("experience",
                                 "fingerprint computation failed; "
                                 "job cold-boots",
                                 job_id=spec.job_id, error=repr(e))
        return CapturedJob(seq=seq, closed_jaxpr=closed,
                           args=(params, opt_state, batch), fingerprint=fp)

    # ------------------------------------------------------------------
    def _capture_serve_spec(self, spec) -> CapturedJob:
        """Resolve a serve spec to ``(serving_engine, requests)`` and build
        the synthetic access sequence standing in for its jaxpr: one
        decode-turn operator touching a full-cache tensor per batch slot.
        ``analyze(..., free_at_last_use=False)`` over it is exactly the
        all-slots-resident KV bound admission should reserve against."""
        from ..service.workloads import resolve_serve_workload
        from .access import Operator, TensorSpec, TensorKind
        engine, requests = resolve_serve_workload(spec)
        sp = spec.serve
        per_seq = engine.bytes_per_token * (sp.prompt_len + sp.gen_len)
        tensors = {
            f"kvslot{i}": TensorSpec(
                tid=f"kvslot{i}", size_bytes=per_seq,
                kind=TensorKind.ACTIVATION, job_id=spec.job_id)
            for i in range(sp.max_sequences)}
        ops = [Operator(idx=0, name="decode_turn", inputs=tuple(tensors),
                        outputs=tuple(tensors), latency=1e-3,
                        job_id=spec.job_id)]
        seq = AccessSequence(spec.job_id, ops, tensors, initial_resident=[])
        return CapturedJob(seq=seq, closed_jaxpr=None,
                           args=(engine, requests), fingerprint=None)

    # ------------------------------------------------------------------
    def predict_peak(self, seq: AccessSequence,
                     budget_hint_bytes: Optional[int] = None
                     ) -> Tuple[int, str]:
        """Admission hook #2: predicted peak bytes for a captured job,
        with its provenance (``"experience"`` or ``"cost-model"``).

        A warm fingerprint returns the measured peak a prior run distilled
        into the ``ExperienceStore``.  Unknown fingerprints get the
        conservative no-free bound from the analyzer (every tensor held to
        its last use), optionally raised to the caller's budget hint — an
        upper bound the admission queue refines from the first profiled
        iteration's measured peak."""
        if self.experience is not None:
            try:
                prior = self.experience.predicted_peak(seq)
                if prior is not None:
                    return prior
            except Exception as e:  # noqa: BLE001 - fall through to model
                self.events.warn("experience",
                                 "predicted-peak prior lookup failed; "
                                 "using cost-model bound",
                                 job_id=seq.job_id, error=repr(e))
        bound = int(analyze([seq], free_at_last_use=False).peak_bytes)
        if budget_hint_bytes:
            bound = max(bound, int(budget_hint_bytes))
        return bound, "cost-model"

    # ------------------------------------------------------------------
    def submit(self, spec, captured: Optional[CapturedJob] = None
               ) -> JobHandle:
        """Register + start a job from a ``JobSpec`` (async, like the
        paper's sub-process per Executor).  The single submission entry
        point shared by in-process callers, the scheduler daemon, and the
        benchmark suite.  ``spec.priority`` feeds the BudgetArbiter's
        priority-weighted policy and PriorityPass victim ordering; when
        None, a priority configured in SchedulerConfig.job_priorities
        (else 1.0) applies.  Pass ``captured`` to reuse a
        ``capture_spec`` result (the daemon captures before admission)."""
        if captured is None:
            captured = self.capture_spec(spec)
        if getattr(spec, "kind", "train") == "serve":
            return self._submit_serve(spec, captured)
        seq, closed = captured.seq, captured.closed_jaxpr
        with self._lock:
            if spec.job_id in self.jobs and not self.jobs[spec.job_id].done:
                raise ValueError(f"job {spec.job_id!r} is already live")
            self.scheduler.register_job(seq, priority=spec.priority)
            eff_priority = self.scheduler.priority_of(spec.job_id)
            # the handle keeps the spec without its payload: the
            # controller owns the job's arrays from here on
            handle = JobHandle(job_id=spec.job_id, seq=seq,
                               closed_jaxpr=closed, args=captured.args,
                               iterations=spec.iterations,
                               priority=eff_priority,
                               spec=dataclasses.replace(spec, payload=None),
                               fingerprint=captured.fingerprint)
            self.jobs[spec.job_id] = handle
            self.ewma[spec.job_id] = EWMATracker(
                alpha=self.scheduler.config.ewma_alpha)
            if self.arbiter is not None:
                # peak demand: predicted vanilla peak until measurements land
                demand = analyze([seq], free_at_last_use=False).peak_bytes
                self.arbiter.register(spec.job_id, priority=eff_priority,
                                      demand_bytes=demand)
            if self.experience is not None:
                # experience priors: a prior run's distilled telemetry
                # for this fingerprint stands in for live samples the
                # job has not produced yet (eor-learned / peak policies)
                try:
                    prior = self.experience.prior(seq)
                    if prior is not None and self.arbiter is not None:
                        self.arbiter.set_prior(spec.job_id, prior)
                except Exception as e:  # noqa: BLE001 - cold boot instead
                    self.experience_failures.append((spec.job_id, e))
                    self.events.warn("experience",
                                     "arbiter prior lookup failed; "
                                     "job starts with live samples only",
                                     job_id=spec.job_id, error=repr(e))
            if self.drift is not None:
                # admission-time prediction pinned for the exit-time
                # comparison (skipped entirely without a monitor)
                try:
                    handle.predicted_peak, _src = self.predict_peak(seq)
                except Exception:  # noqa: BLE001 - drift is best-effort
                    handle.predicted_peak = None
            if spec.schedule:
                self._replan()
        t = threading.Thread(target=self._run_job, args=(handle,), daemon=True)
        handle.thread = t
        t.start()
        return handle

    # ------------------------------------------------------------------
    def _submit_serve(self, spec, captured: CapturedJob) -> JobHandle:
        """Register + start a serving job.  It shares the device ledger,
        DMA channel and arbiter slice with every training job, but its
        residency is planned per decode turn by the serving plane's
        ``KvResidencyPass`` — the iteration-DAG MemoryScheduler never sees
        it (its timeline is a rolling horizon, not a fixed op sequence)."""
        with self._lock:
            if spec.job_id in self.jobs and not self.jobs[spec.job_id].done:
                raise ValueError(f"job {spec.job_id!r} is already live")
            handle = JobHandle(job_id=spec.job_id, seq=captured.seq,
                               closed_jaxpr=None, args=captured.args,
                               iterations=spec.iterations,
                               priority=spec.priority or 1.0, spec=spec)
            self.jobs[spec.job_id] = handle
            if self.arbiter is not None:
                demand = analyze([captured.seq],
                                 free_at_last_use=False).peak_bytes
                self.arbiter.register(spec.job_id,
                                      priority=spec.priority or 1.0,
                                      demand_bytes=demand)
            if spec.schedule:
                self._replan()
        t = threading.Thread(target=self._run_serve_job, args=(handle,),
                             daemon=True)
        handle.thread = t
        t.start()
        return handle

    # ------------------------------------------------------------------
    def _run_serve_job(self, handle: JobHandle) -> None:
        """Thread body for a serving job: hand the request trace to the
        ServingEngine, which drives a ServeSession against OUR ledger and
        channel — KV blocks and training tensors contend for the same
        bytes and the same DMA slot, which is the whole point."""
        try:
            engine, requests = handle.args
            sp = handle.spec.serve
            report, _ = engine.serve(
                requests, budget_bytes=handle.budget_bytes,
                schedule=handle.spec.schedule,
                block_tokens=sp.block_tokens, engine=self.engine,
                job_id=handle.job_id)
            handle.stats.append(report)
            handle.step_times.append(report.total_time)
            handle.peak_bytes = max(handle.peak_bytes, report.peak_bytes)
        except BaseException as e:  # noqa: BLE001 - surfaced via wait()
            handle.error = e
            handle.error_tb = traceback.format_exc()
        finally:
            self._on_job_exit(handle)

    # ------------------------------------------------------------------
    def launch(self, step_fn: Callable, params, opt_state, batch,
               job_id: str, iterations: int = 3,
               schedule: bool = True,
               priority: Optional[float] = None) -> JobHandle:
        """Deprecated shim over :meth:`submit` — build a ``JobSpec`` with
        an in-process payload and submit it.  Kept one release for
        out-of-repo callers; everything in-repo uses ``submit``."""
        warnings.warn(
            "GlobalController.launch(step_fn, ...) is deprecated; build a "
            "repro.service.JobSpec and call GlobalController.submit(spec)",
            DeprecationWarning, stacklevel=2)
        from ..service.jobspec import JobSpec
        spec = JobSpec(job_id=job_id, iterations=iterations,
                       schedule=schedule, priority=priority,
                       payload=(step_fn, params, opt_state, batch))
        return self.submit(spec)

    # ------------------------------------------------------------------
    def _replan(self) -> None:
        """Memory Scheduler pass over all live jobs; distribute plans.

        With an arbiter, the device budget is re-split first (launch,
        finish, and latency drift all funnel through here, so "re-splits on
        every replan" is structural) and the per-job slices are planned
        against.  Executors pick the new plan up at their next iteration
        boundary — `_run_job` reads (plan, version) under the lock only
        between iterations, so a budget move never tears a running one."""
        live = [j for j, h in self.jobs.items() if not h.done]
        if not live:
            return
        with span("tensile.replan", jobs=len(live)):
            self._replan_live(live)

    def _replan_live(self, live: List[str]) -> None:
        budgets: Optional[Dict[str, int]] = None
        prev_assignment: Dict[str, int] = {}
        if self.arbiter is not None:
            for j in live:
                # fold measured peaks (shared-ledger accounting) into demand
                measured = self.accountant.job_peak(j)
                if measured:
                    self.arbiter.update_demand(j, measured)
            prev_assignment = dict(self.arbiter.last_assignment)
            budgets = self.arbiter.split(live)
        # serve jobs take part in the budget split but not in iteration-DAG
        # planning — their per-turn KvResidencyPass plans against the slice
        planned = [j for j in live if not _is_serve(self.jobs[j])]
        if planned:
            plan_budgets = None if budgets is None else {
                j: budgets[j] for j in planned if j in budgets}
            result = self.scheduler.schedule(planned, budgets=plan_budgets)
            for j in planned:
                h = self.jobs[j]
                h.plan = result.plans[j]
                h.plan_version += 1
        for j in live:
            if budgets is not None:
                self.jobs[j].ledger_view = self.accountant.view(
                    j, budgets.get(j))
        self._replan_count += 1
        if (self.arbiter is not None and self.arbiter.mode == "preempt"
                and budgets is not None):
            self._preempt_victims(budgets, prev_assignment)

    # ------------------------------------------------------------------
    def _preempt_victims(self, budgets: Dict[str, int],
                         prev_assignment: Dict[str, int]) -> None:
        """Preemptive arbitration (arbiter mode "preempt"): a launch/burst
        just shrank some live jobs' slices.  Instead of letting each victim
        finish its iteration over-share, build an incremental remainder
        plan (eager swap-outs from the victim's next safe point, via
        ``MemoryScheduler.replan_from``) and hot-swap it into the running
        executor at that safe point.  The boundary plan distributed by
        ``_replan`` still lands at the next iteration — preemption only
        closes the gap until then.  Every future safe point is eligible
        for the splice: if the executor already passed the one the
        remainder plan was built from, events triggered between it and
        the actual splice simply never fire — a bounded, graceful
        degradation (later eager swap-outs still apply, and the boundary
        plan completes the shrink).  Called under the controller lock."""
        # expected footprint under the running plan: live bytes now, or
        # the measured peak so far — a victim below its shrunken slice at
        # this instant can still be heading over it later in the iteration
        usage = {j: max(self.accountant.job_bytes(j),
                        self.accountant.job_peak(j)) for j in budgets}
        for j in self.arbiter.victims(budgets, prev_assignment, usage):
            h = self.jobs.get(j)
            ex = h.executor if h is not None else None
            if ex is None:
                continue            # between iterations: boundary covers it
            running = ex.plan
            safe = find_safe_points(h.seq, running,
                                    source=self.safe_point_source,
                                    telemetry=self.telemetry)
            cur = ex.current_op_index
            future = [sp.op_idx for sp in safe if sp.op_idx > cur]
            if not future:
                continue            # iteration nearly over: boundary covers it
            try:
                with span("tensile.replan", job=j, preempt=True):
                    res = self.scheduler.replan_from(
                        j, running if running is not None
                        else SchedulingPlan(job_id=j),
                        future[0], budgets[j])
            except Exception as e:  # noqa: BLE001 - victim keeps its plan
                self.preempt_failures.append((j, e))
                self.events.warn("preempt",
                                 "incremental preempt replan failed; "
                                 "victim keeps its plan to the boundary",
                                 job_id=j, error=repr(e))
                continue
            prior_n = len(running.events) if running is not None else 0
            if len(res.plans[j].events) == prior_n:
                continue            # remainder already fits: splice is a no-op
            ex.request_plan(res.plans[j], future)
            h.preemptions.append((h.plan_version, future[0]))
            self._preempt_count += 1

    # ------------------------------------------------------------------
    def _run_job(self, handle: JobHandle) -> None:
        try:
            with gc_spans():
                self._iterate_job(handle)
        except BaseException as e:  # noqa: BLE001 - surfaced via wait()
            handle.error = e
            handle.error_tb = traceback.format_exc()
        finally:
            # departure bookkeeping runs for clean finishes AND crashes,
            # outside the job's own try: a failure while replanning the
            # SURVIVORS must not blame this (possibly successful) job
            self._on_job_exit(handle)

    def _iterate_job(self, handle: JobHandle) -> None:
        import jax

        # the executor owns the job's state while it runs: nothing else
        # may hold the arrays, or a swap-out or release would free ledger
        # bytes but not device memory
        params, opt_state, batch = handle.args
        handle.args = None
        p_def = jax.tree.structure(params)
        o_def = jax.tree.structure(opt_state)
        n_p, n_o = p_def.num_leaves, o_def.num_leaves
        state = jax.tree.leaves((params, opt_state))
        del params, opt_state
        ex: Optional[JaxprExecutor] = None
        # the controller's time between two iterations, and its replans,
        # go onto the second one's stats
        t_ret: Optional[float] = None
        replan_s, replans = 0.0, 0
        for it in range(handle.iterations):
            with self._lock:
                plan = handle.plan
                version = handle.plan_version
            with span("tensile.executor_init", job=handle.job_id,
                      iteration=it):
                # fresh per-iteration stores; the host cache (and which
                # parked copies are quantized — fetching them must go
                # through the dequantize path) carries across iterations
                # and plan versions
                host = ex.host if ex is not None else {}
                compressed = (set(ex.ctx.host_compressed)
                              if ex is not None else set())
                ex = JaxprExecutor(
                    handle.closed_jaxpr, handle.seq, plan,
                    accountant=self.accountant, channel=self.channel,
                    async_swap=self.async_swap, telemetry=self.telemetry,
                    iteration=it, plan_version=version,
                    bound_eqns=handle.bound_eqns)
                ex.host.update(host)
                ex.ctx.host_compressed |= compressed
            handle.executor = ex
            # run_flat empties the list it is given: after this line no
            # reference to the iteration's input state is left here
            state.extend(jax.tree.leaves(batch))
            t0 = _time.perf_counter()
            if t_ret is not None:
                ex.stats.before_s = t0 - t_ret
                ex.stats.replan_s, ex.stats.replans = replan_s, replans
            outs = ex.run_flat(state)
            t_ret = _time.perf_counter()
            handle.step_times.append(t_ret - t0)
            handle.stats.append(ex.stats)
            handle.peak_bytes = max(handle.peak_bytes, ex.stats.peak_bytes)
            # feed params/opt-state back (outputs 0,1 by convention); the
            # rest (the loss) is what the step reports
            state = outs[:n_p + n_o]
            handle.outputs.append(outs[n_p + n_o:])
            del outs
            # measured-telemetry feedback (paper step 4): the hub already
            # holds this iteration's op samples; fold them into the job's
            # sequence and replan on HUB-reported drift (the
            # scheduler-private EWMA path stays available as
            # report_latencies for embedders without a hub)
            with span("tensile.report_telemetry", job=handle.job_id):
                drift = self.report_telemetry(handle.job_id)
            replan_s, replans = 0.0, 0
            if drift:
                with self._lock:
                    t = _time.perf_counter()
                    self._replan()
                    replan_s, replans = _time.perf_counter() - t, 1
            ex.close()
        handle.args = (jax.tree.unflatten(p_def, state[:n_p]),
                       jax.tree.unflatten(o_def, state[n_p:]), batch)

    # ------------------------------------------------------------------
    def _on_job_exit(self, handle: JobHandle) -> None:
        """Departure bookkeeping: deregister from scheduler + arbiter and
        redistribute the departed job's slice across the survivors.  A job
        that held ZERO bytes of the split (a finished under-demand job)
        reclaims nothing — re-splitting and replanning every survivor
        would rebuild the exact same plans, so the no-op replan is
        skipped."""
        handle.done = True
        handle.executor = None
        is_serve = _is_serve(handle)
        with self._lock:
            if self.experience is not None and not is_serve:
                # flush distilled experience BEFORE deregistering: the
                # hub still holds this job's records, the handle its
                # final plan.  Failures are recorded, never raised — the
                # store must not take a (possibly successful) job down.
                try:
                    self.cost_model.recalibrate(self.telemetry,
                                                report=False)
                    fp = handle.fingerprint \
                        or self.experience.fingerprint(handle.seq)
                    samples = self.telemetry.total_op_samples()
                    self.experience.record_job(
                        fp, seq=handle.seq, hub=self.telemetry,
                        job_id=handle.job_id, plan=handle.plan,
                        pipeline=self.scheduler.pipeline.name,
                        peak_bytes=max(
                            handle.peak_bytes,
                            self.accountant.job_peak(handle.job_id)),
                        calib=self.cost_model.calib,
                        calib_samples=samples)
                    self.experience.flush()
                except Exception as e:  # noqa: BLE001
                    self.experience_failures.append((handle.job_id, e))
                    self.events.warn("experience",
                                     "experience flush failed on job "
                                     "exit; distilled run lost",
                                     job_id=handle.job_id, error=repr(e))
            if self.drift is not None and not is_serve \
                    and handle.predicted_peak:
                measured = max(handle.peak_bytes,
                               self.accountant.job_peak(handle.job_id))
                if measured > 0:
                    fp = handle.fingerprint or ""
                    if not fp and self.experience is not None:
                        try:
                            fp = self.experience.fingerprint(handle.seq)
                        except Exception:  # noqa: BLE001
                            fp = ""
                    self.drift.observe(
                        fp or handle.job_id,
                        predicted_peak=handle.predicted_peak,
                        measured_peak=measured, job_id=handle.job_id)
                    if self.experience is not None:
                        try:  # persist the drift history now, not at
                            # the NEXT job's flush
                            self.experience.flush()
                        except Exception as e:  # noqa: BLE001
                            self.events.warn(
                                "experience", "drift-history flush "
                                "failed", job_id=handle.job_id,
                                error=repr(e))
            if not is_serve:
                self.scheduler.remove_job(handle.job_id)
            if self.arbiter is not None:
                reclaimed = self.arbiter.last_assignment.get(
                    handle.job_id, 0)
                self.arbiter.unregister(handle.job_id)
                if reclaimed == 0:
                    return
                try:
                    self._replan()
                except Exception as e:  # noqa: BLE001
                    # survivors keep their current (still valid) plans
                    self.replan_failures.append((handle.job_id, e))
                    self.events.warn("replan",
                                     "survivor replan failed after job "
                                     "departure; current plans kept",
                                     job_id=handle.job_id, error=repr(e))

    # ------------------------------------------------------------------
    def report_latencies(self, job_id: str, measured: List[float]) -> bool:
        with self._lock:
            if job_id not in self.scheduler.jobs:
                return False
            return self.scheduler.update_latencies(job_id, measured)

    def report_telemetry(self, job_id: str) -> bool:
        """Fold the hub's measured latencies into the job's sequence and
        return whether the hub reports drift past the replan threshold.
        The cost model recalibrates from the same new samples (O(new
        samples), per-job cursors), closing the capture-time loop: the
        NEXT ``launch()`` estimates latencies from measured constants,
        not the probe defaults the process started with."""
        with self._lock:
            if job_id not in self.scheduler.jobs:
                return False
            self.cost_model.recalibrate(self.telemetry, report=False)
            return self.scheduler.update_latencies_from_hub(
                job_id, self.telemetry)

    def failures(self) -> Dict[str, BaseException]:
        """Failed jobs so far (job_id -> exception)."""
        return {j: h.error for j, h in self.jobs.items()
                if h.error is not None}

    def wait(self, timeout: Optional[float] = None,
             raise_errors: bool = True) -> None:
        """Join every job thread, then surface job-thread failures loudly:
        all of them at once (``JobFailedError.failures``/``tracebacks``),
        with the first original exception chained as the cause.  Failures
        are raised even when the timeout expires before every thread
        joins — a dead job must not be masked by a slow one."""
        deadline = None if timeout is None else _time.time() + timeout
        for h in list(self.jobs.values()):
            if h.thread is None:
                continue
            remaining = None if deadline is None else max(0.0, deadline - _time.time())
            h.thread.join(remaining)
        failures = self.failures()
        if failures and raise_errors:
            tbs = {j: self.jobs[j].error_tb for j in failures
                   if self.jobs[j].error_tb}
            err = JobFailedError(failures, tbs)
            raise err from next(iter(failures.values()))

    @property
    def global_peak_bytes(self) -> int:
        return self.accountant.peak

    @property
    def replan_count(self) -> int:
        return self._replan_count

    @property
    def preempt_count(self) -> int:
        """Mid-iteration plan hot-swaps requested so far (arbiter mode
        "preempt")."""
        return self._preempt_count
