"""Operator latency estimation (paper §IV-C) and the cold-start predictor.

Three layers, used in this order:
  1. **Analytic model** — per-primitive FLOPs / bytes from the jaxpr equation,
     latency = max(flops/peak_flops, bytes/mem_bw) scaled by a utilization
     factor.  Available before anything has ever run (cold start floor).
  2. **MLP predictor** — the paper's light 3-layer MLP mapping
     <input dims…, op params…, device utilization> → latency, trained on
     measured samples collected at system initialization.  Implemented in
     pure JAX (no framework), trained with the repo's own Adam.
  3. **EWMA correction** — at runtime, measured latencies are folded in with
     an exponentially weighted moving average (paper §IV-E); this dominates
     once a job is past its first steps.
"""
from __future__ import annotations

import dataclasses
import math
import time as _time
from typing import Dict, Optional, Tuple

import numpy as np

# row-block width of the quantize-on-offload Pallas kernel
# (kernels/offload_quant.BLOCK; duplicated so this module stays jax-free)
OFFLOAD_QUANT_BLOCK = 512

ELEMENTWISE_FLOPS = {
    "add": 1, "sub": 1, "mul": 1, "div": 1, "max": 1, "min": 1, "neg": 1,
    "exp": 8, "log": 8, "tanh": 10, "logistic": 10, "erf": 10, "rsqrt": 4,
    "sqrt": 4, "pow": 10, "integer_pow": 2, "abs": 1, "sign": 1,
    "floor": 1, "ceil": 1, "round": 1, "is_finite": 1, "and": 1, "or": 1,
    "xor": 1, "not": 1, "select_n": 1, "clamp": 2, "add_any": 1, "cos": 8,
    "sin": 8, "eq": 1, "ne": 1, "ge": 1, "gt": 1, "le": 1, "lt": 1,
}


def _numel(aval) -> int:
    try:
        return int(np.prod(aval.shape)) if aval.shape else 1
    except Exception:
        return 1


def _nbytes(aval) -> int:
    try:
        return _numel(aval) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


@dataclasses.dataclass
class DeviceCalibration:
    """Effective throughput of the executing device.  Defaults are calibrated
    for this container's CPU at import time of the benchmarks (cheap matmul /
    memcpy probes); the TPU target constants live in plan.MachineProfile.

    Beyond the import-time probes, the constants recalibrate ONLINE from
    measured telemetry: ``CostModel.recalibrate(hub)`` folds every new
    TelemetryHub op sample (measured latency + the op's static
    flops/bytes) into ``flops`` / ``mem_bw`` with an EWMA, so the model
    tracks the device it is actually running on instead of the device it
    was probed on."""
    flops: float = 5e10
    mem_bw: float = 1e10
    overhead_s: float = 2e-6


@dataclasses.dataclass
class CalibrationReport:
    """How well the (re)calibrated analytic model predicts the measured
    latencies in a TelemetryHub: mean relative error overall and per
    primitive.  Exposed so the benchmarks/CI can gate on calibration
    quality (`calib_err` in BENCH_scenarios.json)."""

    overall: float                       # mean |pred - measured| / measured
    per_primitive: Dict[str, float]
    samples: int


def _clamped(estimate: float, current: float, limit: float = 16.0) -> float:
    """Bound a single-sample throughput point-estimate to within
    ``limit``x of the current constant: one outlier (GC pause, cold
    cache) must not move the calibration by orders of magnitude — the
    EWMA then walks toward a persistent shift over several samples."""
    return min(max(estimate, current / limit), current * limit)


class CostModel:
    def __init__(self, calib: Optional[DeviceCalibration] = None,
                 experience=None):
        # warm boot (experience plane): with no explicit calibration, an
        # attached ExperienceStore supplies the constants persisted by a
        # prior run's recalibration — capture-time latency estimates then
        # flow through measured experience instead of probe defaults.
        # An explicit `calib` always wins (the caller knows better).
        if calib is None and experience is not None:
            try:
                calib = experience.device_calibration()
            except Exception:   # noqa: BLE001 - corrupt store: cold boot
                calib = None
        self.calib = calib or DeviceCalibration()
        self.experience = experience
        self.mlp: Optional["LatencyMLP"] = None
        self.utilization: float = 0.0  # 0..1, "GPU usage" analogue
        # recalibration cursor per job: only hub samples newer than this
        # are folded in on the next recalibrate() call
        self._recal_cursor: Dict[str, int] = {}

    # ------------------------------------------------------------------
    def eqn_cost(self, eqn) -> Tuple[float, float]:
        """(flops, bytes) for one jaxpr equation."""
        prim = eqn.primitive.name
        out_avals = [v.aval for v in eqn.outvars]
        in_avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
        out_n = sum(_numel(a) for a in out_avals)
        in_b = sum(_nbytes(a) for a in in_avals)
        out_b = sum(_nbytes(a) for a in out_avals)
        bts = in_b + out_b
        if prim == "dot_general":
            dnums = eqn.params["dimension_numbers"]
            (lc, rc), (lb, rb) = dnums
            lhs = in_avals[0]
            contract = 1
            for d in lc:
                contract *= lhs.shape[d]
            flops = 2.0 * out_n * contract
        elif prim in ("conv_general_dilated",):
            rhs = in_avals[1]
            flops = 2.0 * out_n * _numel(rhs) / max(rhs.shape[-1], 1)
        elif prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                      "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax"):
            flops = float(sum(_numel(a) for a in in_avals))
        elif prim in ("custom_jvp_call", "custom_vjp_call", "jit",
                      "closed_call", "remat2", "scan", "while", "cond"):
            # nested jaxpr: the sum of its equations (``jit`` is a nested
            # jax.jit call, ``remat2`` a jax.checkpoint region)
            flops, extra_b = self._call_cost(eqn)
            bts = max(bts, extra_b)
        else:
            flops = float(out_n) * ELEMENTWISE_FLOPS.get(prim, 1)
        return flops, float(bts)

    def _call_cost(self, eqn) -> Tuple[float, float]:
        flops, bts = 0.0, 0.0
        for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(key)
            if sub is None:
                continue
            jaxpr = getattr(sub, "jaxpr", sub)
            for e in getattr(jaxpr, "eqns", []):
                f, b = self.eqn_cost(e)
                flops += f
                bts += b
        for key in ("branches",):
            for sub in eqn.params.get(key, ()):
                jaxpr = getattr(sub, "jaxpr", sub)
                for e in getattr(jaxpr, "eqns", []):
                    f, b = self.eqn_cost(e)
                    flops += f
                    bts += b
        n_iter = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        return flops * n_iter, bts * n_iter

    # ------------------------------------------------------------------
    def offload_quant_latency(self, size_bytes: int) -> float:
        """Latency of the quantize-on-offload Pallas kernel
        (kernels/offload_quant: per 1×512 tile, absmax → scale → int8 pack).

        The kernel is bandwidth-bound: it reads the source tensor once and
        writes int8 + one fp32 scale per block (≈1.25× the source bytes
        moved for fp32 input), plus a small per-block issue overhead.  Used
        by CompressedOffloadPass to price the compressed swap path and to
        calibrate MachineProfile.offload_quant_bw."""
        c = self.calib
        block_bytes = 4 * OFFLOAD_QUANT_BLOCK
        blocks = max(1, math.ceil(size_bytes / block_bytes))
        moved = size_bytes * (1.0 + 0.25 + 4.0 / block_bytes)
        return c.overhead_s + moved / c.mem_bw + blocks * 2e-9

    def offload_quant_bandwidth(self, probe_bytes: int = 16 << 20) -> float:
        """Effective source-bytes/s of the quantize path — plug into
        MachineProfile.offload_quant_bw so the planner's compressed swap
        times match this device."""
        return probe_bytes / max(self.offload_quant_latency(probe_bytes),
                                 1e-12)

    # ------------------------------------------------------------------
    def dma_batch_latency(self, sizes, profile) -> float:
        """Modeled latency of one coalesced DMA batch (the runtime's
        ``DmaChannel.acquire_batch`` booking): one link setup, the summed
        payload at link bandwidth, and ``profile.dma_batch_overhead`` per
        extra member."""
        return profile.batched_swap_time(sizes)

    def dma_batch_saving(self, n_members: int, profile) -> float:
        """Latency saved by coalescing ``n_members`` adjacent transfers
        into one batch: (n-1) per-transfer setups collapse to (n-1)
        per-member descriptor fixups.  The serving plane's batched
        evict/fetch cohorts are priced with exactly this term."""
        if n_members <= 1:
            return 0.0
        return (n_members - 1) * max(
            profile.host_link_latency - profile.dma_batch_overhead, 0.0)

    # ------------------------------------------------------------------
    def latency(self, flops: float, bytes_accessed: float,
                prim_name: str = "") -> float:
        """Roofline latency under current utilization; if the MLP predictor
        is trained, blend it in (cold-start path, paper §IV-C)."""
        c = self.calib
        slowdown = 1.0 + self.utilization  # contended device runs slower
        base = c.overhead_s + slowdown * max(flops / c.flops,
                                             bytes_accessed / c.mem_bw)
        if self.mlp is not None:
            pred = self.mlp.predict_one(flops, bytes_accessed, self.utilization)
            if pred > 0:
                return float(0.5 * base + 0.5 * pred)
        return float(base)

    # ------------------------------------------------------------------
    # Online recalibration from measured telemetry (the §IV-E feedback
    # loop widened from per-op latencies to the throughput constants)
    # ------------------------------------------------------------------
    def recalibrate(self, hub, alpha: float = 0.5,
                    report: bool = True) -> Optional["CalibrationReport"]:
        """Fold every NEW TelemetryHub op sample into the calibration:
        each measured (flops, bytes, latency) triple yields a point
        estimate of the constant its roofline term is bound by — the
        classification uses the current calibration, so consistent
        samples contract both constants geometrically toward the device's
        effective throughput.  Samples already consumed (per-job cursor)
        are skipped, so the controller can call this after every
        iteration at O(new samples) cost; so are cold samples, whose
        latency holds a compilation (``OpSample.cold``).  Returns the
        post-update ``calibration_report`` — unless ``report=False``, which
        keeps the whole call O(new samples) for per-iteration callers (the
        report re-scans every sample)."""
        c = self.calib
        for job_id in hub.jobs():
            samples = hub.ops.get(job_id, ())
            start = self._recal_cursor.get(job_id, 0)
            for s in samples[start:]:
                eff = s.latency_s - c.overhead_s
                if s.cold or eff <= 0 or (s.flops <= 0
                                          and s.bytes_accessed <= 0):
                    continue
                if eff < 0.25 * s.latency_s:
                    # overhead-dominated sample: measurement jitter of
                    # the same order as `eff` would make the throughput
                    # estimate unbounded — no signal, skip it
                    continue
                if s.flops / c.flops >= s.bytes_accessed / c.mem_bw:
                    est = _clamped(s.flops / eff, c.flops)
                    c.flops = (1 - alpha) * c.flops + alpha * est
                else:
                    est = _clamped(s.bytes_accessed / eff, c.mem_bw)
                    c.mem_bw = (1 - alpha) * c.mem_bw + alpha * est
            self._recal_cursor[job_id] = len(samples)
        return self.calibration_report(hub) if report else None

    def calibration_report(self, hub) -> "CalibrationReport":
        """Per-primitive relative error of the analytic model against the
        hub's measured latencies (utilization-free prediction: the error
        isolates the throughput constants, not the contention factor)."""
        util, self.utilization = self.utilization, 0.0
        try:
            errs: Dict[str, list] = {}
            for job_id in hub.jobs():
                for s in hub.ops.get(job_id, ()):
                    if s.latency_s <= 0 or (s.flops <= 0
                                            and s.bytes_accessed <= 0):
                        continue
                    pred = self.latency(s.flops, s.bytes_accessed, s.prim)
                    rel = abs(pred - s.latency_s) / s.latency_s
                    errs.setdefault(s.prim or "?", []).append(rel)
        finally:
            self.utilization = util
        per_prim = {p: sum(v) / len(v) for p, v in errs.items()}
        n = sum(len(v) for v in errs.values())
        overall = (sum(sum(v) for v in errs.values()) / n) if n else 0.0
        return CalibrationReport(overall=overall, per_primitive=per_prim,
                                 samples=n)


# ======================================================================
# The paper's 3-layer MLP latency predictor, in pure JAX.
# ======================================================================
class LatencyMLP:
    """Predicts log-latency from <log flops, log bytes, utilization>.

    The paper feeds raw input dims + op params; flops/bytes are a sufficient
    statistic of those for roofline-dominated ops and keep the model
    op-agnostic.  3 layers, as in the paper.
    """

    def __init__(self, hidden: int = 32, seed: int = 0):
        import jax
        import jax.numpy as jnp
        self.jnp = jnp
        self.jax = jax
        k = jax.random.PRNGKey(seed)
        k1, k2, k3 = jax.random.split(k, 3)
        s = 1 / math.sqrt(3)
        self.params = {
            "w1": jax.random.normal(k1, (3, hidden)) * s,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, hidden)) / math.sqrt(hidden),
            "b2": jnp.zeros((hidden,)),
            "w3": jax.random.normal(k3, (hidden, 1)) / math.sqrt(hidden),
            "b3": jnp.zeros((1,)),
        }
        self._jit_pred = jax.jit(self._forward)

    @staticmethod
    def featurize(flops: np.ndarray, bytes_: np.ndarray,
                  util: np.ndarray) -> np.ndarray:
        return np.stack([np.log1p(flops) / 30.0, np.log1p(bytes_) / 30.0,
                         util], axis=-1).astype(np.float32)

    def _forward(self, params, x):
        jnp = self.jnp
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        h = jnp.tanh(h @ params["w2"] + params["b2"])
        return (h @ params["w3"] + params["b3"])[..., 0]

    def fit(self, flops: np.ndarray, bytes_: np.ndarray, util: np.ndarray,
            latency_s: np.ndarray, steps: int = 2000, lr: float = 3e-3) -> float:
        """Train on measured samples; returns training R² on log-latency."""
        jax, jnp = self.jax, self.jnp
        x = jnp.asarray(self.featurize(flops, bytes_, util))
        y = jnp.asarray(np.log(np.maximum(latency_s, 1e-9)).astype(np.float32))

        def loss_fn(p):
            pred = self._forward(p, x)
            return jnp.mean((pred - y) ** 2)

        from repro.optim.adam import adamw_init, adamw_update
        state = adamw_init(self.params)
        p = self.params
        vg = jax.jit(jax.value_and_grad(loss_fn))

        @jax.jit
        def step(p, state):
            l, g = jax.value_and_grad(loss_fn)(p)
            p, state = adamw_update(p, g, state, lr=lr, weight_decay=0.0)
            return p, state, l

        for _ in range(steps):
            p, state, l = step(p, state)
        self.params = p
        pred = np.asarray(self._forward(p, x))
        yn = np.asarray(y)
        ss_res = float(np.sum((pred - yn) ** 2))
        ss_tot = float(np.sum((yn - yn.mean()) ** 2)) or 1e-12
        return 1.0 - ss_res / ss_tot

    def predict_one(self, flops: float, bytes_: float, util: float) -> float:
        x = self.jnp.asarray(self.featurize(
            np.array([flops]), np.array([bytes_]), np.array([util])))
        return float(np.exp(np.asarray(self._jit_pred(self.params, x))[0]))

    def r2(self, flops, bytes_, util, latency_s) -> float:
        x = self.jnp.asarray(self.featurize(np.asarray(flops), np.asarray(bytes_),
                                            np.asarray(util)))
        pred = np.asarray(self._jit_pred(self.params, x))
        y = np.log(np.maximum(np.asarray(latency_s), 1e-9))
        ss_res = float(np.sum((pred - y) ** 2))
        ss_tot = float(np.sum((y - y.mean()) ** 2)) or 1e-12
        return 1.0 - ss_res / ss_tot


class EWMATracker:
    """Runtime latency correction (paper §IV-E)."""

    def __init__(self, alpha: float = 0.3):
        self.alpha = alpha
        self.values: Dict[int, float] = {}
        self._hub_cursor: Dict[str, int] = {}

    def update(self, op_idx: int, measured: float) -> float:
        old = self.values.get(op_idx)
        new = measured if old is None else (
            self.alpha * measured + (1 - self.alpha) * old)
        self.values[op_idx] = new
        return new

    def ingest(self, hub, job_id: str) -> int:
        """Fold every NEW warm TelemetryHub op sample of the job into the
        tracker (per-job cursor, O(new samples); cold samples, which hold
        a compilation, are passed over); returns how many were folded.
        This is the hub-fed path of §IV-E — the tracker no longer needs
        the executor to hand it latency lists directly."""
        samples = hub.ops.get(job_id, ())
        start = self._hub_cursor.get(job_id, 0)
        warm = [s for s in samples[start:] if not s.cold]
        for s in warm:
            self.update(s.op_idx, s.latency_s)
        self._hub_cursor[job_id] = len(samples)
        return len(warm)

    def drift_ratio(self, baseline_sum: float) -> float:
        s = sum(self.values.values())
        if baseline_sum <= 0:
            return float("inf")
        return abs(s - baseline_sum) / baseline_sum


def calibrate_cpu(n: int = 256) -> DeviceCalibration:
    """Measure this container's effective matmul flops + memcpy bandwidth so
    the analytic model predicts realistic CPU latencies for the benchmarks."""
    a = np.random.rand(n, n).astype(np.float32)
    b = np.random.rand(n, n).astype(np.float32)
    t0 = _time.perf_counter()
    reps = 20
    for _ in range(reps):
        a @ b
    dt = (_time.perf_counter() - t0) / reps
    flops = 2 * n ** 3 / max(dt, 1e-9)
    big = np.random.rand(4 << 20).astype(np.float32)
    t0 = _time.perf_counter()
    for _ in range(10):
        big.copy()
    bw = 10 * big.nbytes * 2 / max(_time.perf_counter() - t0, 1e-9)
    return DeviceCalibration(flops=flops, mem_bw=bw)
