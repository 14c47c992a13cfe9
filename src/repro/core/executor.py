"""Interpreting Executor (paper §III-D: Executor + Swap Executor).

Runs a captured jaxpr equation-by-equation against the shared MemoryEngine:
the engine's DeviceLedger does the byte-exact residency accounting, its
DmaChannel serializes transfers, and its JobContext supplies every residency
*decision* (when a planned event applies, when an operand needs a passive
swap-in or a recompute, when a tensor auto-releases) — the same rules the
discrete-event simulator runs, so simulated and real executions of a plan
agree by construction (tests/test_engine_parity.py).

On this container "device" and "host" are both CPU RAM, so residency is
tracked logically (exact aval bytes) while the *data path* is real: swapped
tensors are copied into the host store, dropped from the device store, and
swapped back (or recomputed from their producer equation) before use;
compressed events round-trip through the Pallas quantize-on-offload kernels.
Final outputs are verified against an un-scheduled reference execution.

Both stores are keyed by **storage id**: an updated parameter aliases the old
parameter's storage (paper §IV-B situation 2), so the Opt-phase update
overwrites in place instead of double-counting.

Two swap modes:
  * sync  — swap events execute inline at their trigger (deterministic;
            tests and the parity check against simulate(transfer_mode="sync")).
  * async — a Swap Executor thread drains an event queue while compute
            proceeds, serialized by the engine channel (paper Fig. 4); used
            by the multi-workload runtime for real overlap and contention.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time as _time
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import numpy as np
from jax.extend import core as jcore

from .access import AccessSequence
from .engine import (INPUT_AWAIT_PREFETCH, INPUT_PASSIVE_SWAP_IN,
                     INPUT_RESIDENT, DeviceLedger, DmaChannel, MemoryEngine,
                     ResidencyView)
from .plan import EventType, SchedulingPlan
from .telemetry import TelemetryHub

# Back-compat names: the seed defined these locally; they now live in (and
# are shared through) the engine.
DeviceAccountant = DeviceLedger
SwapChannel = DmaChannel


@dataclasses.dataclass
class ExecutionStats:
    peak_bytes: int = 0
    wall_time_s: float = 0.0
    swap_out_count: int = 0
    swap_in_count: int = 0
    passive_swap_ins: int = 0
    recompute_count: int = 0
    compressed_swaps: int = 0
    op_latencies: Optional[List[float]] = None
    stall_time_s: float = 0.0
    # mid-iteration plan hot-swaps applied at a safe point
    hot_swaps: int = 0
    # queued (unstarted) prefetches cancelled when a hot-swap revised
    # swap-INs already booked on the channel
    canceled_swap_ins: int = 0
    # measured per-job residency timeline of THIS iteration, (t, bytes)
    # in hub time — filled from the TelemetryHub when one is attached
    residency_timeline: Optional[List[tuple]] = None


class AsyncSwapExecutor:
    """Paper Fig. 4: an execution-queue thread pops swap events and runs them
    on the shared engine channel.

    The worker is a double-buffered stream: after popping one transfer it
    non-blockingly drains any *same-direction* transfers already queued
    behind it and runs the whole cohort as ONE ``channel.transfer_batch``
    launch — queued prefetches coalesce on the wire instead of paying one
    channel round-trip each.  ``batches`` traces each coalesced launch
    (the regression test asserts two queued prefetches share one)."""

    MAX_BATCH = 8

    def __init__(self, channel: DmaChannel):
        self.channel = channel
        self.q: "queue.Queue" = queue.Queue()
        self.inflight: Dict[str, threading.Event] = {}
        self._stop = False
        # state_lock guards running/poisoned: `running` holds the keys
        # whose transfers are physically on the wire (a coalesced batch
        # carries several); `poisoned` keys were cancelled after the
        # worker popped them but before it started — the worker discards
        # them instead of transferring
        self.state_lock = threading.Lock()
        self.running: set = set()
        self.poisoned: set = set()
        # keys of each coalesced launch, in completion order
        self.batches: List[List[str]] = []
        # one popped-but-deferred item of the OTHER direction (keeps FIFO
        # order across direction changes without a peekable queue)
        self._carry = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def submit(self, key: str, fn) -> threading.Event:
        done = threading.Event()
        self.inflight[key] = done
        self.q.put((key, fn, done))
        return done

    @staticmethod
    def _direction(key: str) -> str:
        return key.split(":", 1)[0]

    def _run(self):
        while not self._stop:
            if self._carry is not None:
                item, self._carry = self._carry, None
            else:
                try:
                    item = self.q.get(timeout=0.05)
                except queue.Empty:
                    continue
            batch = [item]
            prefix = self._direction(item[0])
            while len(batch) < self.MAX_BATCH:
                try:
                    nxt = self.q.get_nowait()
                except queue.Empty:
                    break
                if self._direction(nxt[0]) == prefix:
                    batch.append(nxt)
                else:
                    self._carry = nxt
                    break
            live = []
            with self.state_lock:
                for key, fn, done in batch:
                    if key in self.poisoned:
                        self.poisoned.discard(key)
                        done.set()
                        self.inflight.pop(key, None)
                    else:
                        live.append((key, fn, done))
                        self.running.add(key)
            if not live:
                continue
            try:
                if len(live) == 1:
                    self.channel.transfer(live[0][1])
                else:
                    self.channel.transfer_batch([fn for _, fn, _ in live])
            finally:
                with self.state_lock:
                    for key, _, _ in live:
                        self.running.discard(key)
                self.batches.append([key for key, _, _ in live])
                for key, _, done in live:
                    done.set()
                    self.inflight.pop(key, None)

    def cancel_unstarted(self, prefix: str = "") -> Optional[List[str]]:
        """Cancel every transfer whose key starts with ``prefix`` that
        has NOT physically started — queued items are drained, items the
        worker already popped (but not started) are poisoned so it
        discards them.  Returns None WITHOUT cancelling anything when a
        matching transfer is on the wire (the caller must defer), else
        the cancelled keys.  Waiters are released — ``_ensure_input``
        re-derives the action, so a consumer of a cancelled prefetch
        falls back to a passive swap-in."""
        with self.state_lock:
            if any(k.startswith(prefix) for k in self.running):
                return None
            cancelled: List[str] = []
            requeue = []
            while True:
                try:
                    item = self.q.get_nowait()
                except queue.Empty:
                    break
                key, _fn, done = item
                if key.startswith(prefix):
                    cancelled.append(key)
                    self.inflight.pop(key, None)
                    done.set()
                else:
                    requeue.append(item)
            for item in requeue:
                self.q.put(item)
            # popped-but-unstarted items (incl. a carried one) are blocked
            # on state_lock right now: poison them, the worker will
            # discard and release them
            for key in list(self.inflight):
                if key.startswith(prefix) and key not in self.running:
                    self.poisoned.add(key)
                    cancelled.append(key)
            return cancelled

    def drain(self):
        # every submitted-but-unfinished key sits in `inflight` until its
        # completion event fires — wait on the events themselves instead
        # of busy-polling the queue
        while self.inflight:
            for ev in list(self.inflight.values()):
                ev.wait()

    def stop(self):
        self.drain()
        self._stop = True
        # the worker's last batch holds transfer closures, and through
        # them the executor's value store: wait until it lets them go
        self.thread.join()


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


class JaxprExecutor:
    def __init__(self, closed_jaxpr, seq: AccessSequence,
                 plan: Optional[SchedulingPlan] = None,
                 accountant: Optional[DeviceLedger] = None,
                 channel: Optional[DmaChannel] = None,
                 async_swap: bool = False,
                 measure_latency: bool = False,
                 host_resident_inputs: Optional[Set[str]] = None,
                 engine: Optional[MemoryEngine] = None,
                 telemetry: Optional[TelemetryHub] = None):
        self.closed = closed_jaxpr
        self.jaxpr = closed_jaxpr.jaxpr
        self.seq = seq
        self.plan = plan
        self.engine = engine or MemoryEngine(ledger=accountant,
                                             channel=channel)
        if telemetry is not None:
            self.engine.attach_telemetry(telemetry)
        self.telemetry = self.engine.telemetry
        self.ctx = self.engine.add_job(seq, plan)
        self.accountant = self.engine.ledger
        self.channel = self.engine.channel
        self.async_exec = AsyncSwapExecutor(self.channel) if async_swap else None
        self.measure_latency = measure_latency
        # storages whose *input* value starts on host (previous iteration's
        # cross-iteration swap-out; paper Fig. 1(c) steady state)
        self.host_resident_inputs: Set[str] = set(host_resident_inputs or ())

        self.device: Dict[str, Any] = {}
        self.host: Dict[str, Any] = {}
        # double-buffered swap-outs: storage -> (completion event,
        # compressed, the storage's write count when the copy began).  The
        # device copy is retired (trace record, ledger free, stats) only
        # when the copy has landed — observed at the next completion-poll
        # point instead of a blocking wait.
        self._pending_out: Dict[str, Tuple[threading.Event, bool, int]] = {}
        # per storage, how many values have been written to the device:
        # an in-place update while a swap-out is in flight shows as a
        # changed count at retirement
        self._writes: Dict[str, int] = {}
        # decisions consult THIS iteration's value store, not the (possibly
        # longer-lived, controller-shared) ledger
        self.resident = ResidencyView(self.device)

        self.var_by_name: Dict[str, Any] = {}
        self._name: Dict[Any, str] = {}
        # naming order must match graph_capture.capture exactly
        for v in list(self.jaxpr.invars) + list(self.jaxpr.constvars):
            self._name_of(v)
        for eqn in self.jaxpr.eqns:
            for v in eqn.outvars:
                self._name_of(v)

        self.producer: Dict[str, int] = {}
        for i, eqn in enumerate(self.jaxpr.eqns):
            for v in eqn.outvars:
                self.producer[self._name_of(v)] = i
        self.stats = ExecutionStats(op_latencies=[] if measure_latency else None)
        self._cur_idx = -1
        # pending mid-iteration plan hot-swap: (plan, eligible safe ops),
        # set by the controller thread, consumed at a safe point in run()
        self._plan_lock = threading.Lock()
        self._pending_plan: Optional[Tuple[SchedulingPlan, frozenset]] = None

    # ------------------------------------------------------------------
    @property
    def current_op_index(self) -> int:
        """Index of the equation being executed (-1 before the first) —
        the controller reads this to pick a safe point still ahead of the
        run when requesting a preemptive plan hot-swap."""
        return self._cur_idx

    def request_plan(self, plan: SchedulingPlan,
                     safe_ops) -> None:
        """Thread-safe mid-iteration plan hot-swap request (preemptive
        arbitration).  The new plan is spliced in at the next safe point
        the run reaches: an op boundary in ``safe_ops`` with no transfer
        of this job in flight.  A later request supersedes an unapplied
        earlier one.  If no listed safe point remains this iteration, the
        request simply never fires — the boundary plan pickup covers it."""
        with self._plan_lock:
            self._pending_plan = (plan, frozenset(safe_ops))

    def _maybe_hot_swap(self, idx: int) -> None:
        """Splice the pending plan in if op boundary `idx` is an eligible
        safe point.  Runs on the executor thread right after the op's plan
        events, mirroring the simulator's splice instant exactly.

        Swap-INs already booked on the channel do not block the splice:
        queued prefetches the Swap Executor has not started yet are
        CANCELLED (the new plan re-books what it still needs; a consumer
        of a cancelled prefetch degrades to a passive swap-in) — only a
        transfer physically in progress defers the splice to the next
        safe point."""
        if self._pending_plan is None:
            return
        with self._plan_lock:
            if self._pending_plan is None:
                return
            plan, safe_ops = self._pending_plan
            if idx not in safe_ops:
                return
            # a splice needs quiescence: wait out our own in-flight
            # swap-outs (short copies; the pre-double-buffer executor
            # blocked on them at issue time, so this preserves the PR-4
            # cancel/defer semantics exactly)
            self._poll_swap_outs(block=True)
            if self.async_exec and self.async_exec.inflight:
                cancelled = self.async_exec.cancel_unstarted("in:")
                if cancelled is None:
                    # a prefetch is physically on the wire: defer to the
                    # next safe point.  cancel_unstarted cancels NOTHING
                    # in that case, so the still-running old plan keeps
                    # every prefetch it queued.
                    return
                with self.async_exec.state_lock:
                    blocking = [k for k in self.async_exec.inflight
                                if k not in self.async_exec.poisoned]
                if blocking:
                    return       # e.g. a swap-out raced in: next point
                self.stats.canceled_swap_ins += len(cancelled)
            self.plan = plan
            self.ctx.set_plan(plan)
            self.stats.hot_swaps += 1
            self._pending_plan = None
            rec = self.engine.recorder
            if rec is not None:
                t = self.telemetry.now() if self.telemetry is not None \
                    else 0.0
                rec.instant("hot_swap", t, job_id=self.ctx.job_id,
                            site="safe-point", op_idx=idx)

    # ------------------------------------------------------------------
    def _name_of(self, v) -> str:
        if v not in self._name:
            nm = f"v{len(self._name)}"
            self._name[v] = nm
            self.var_by_name[nm] = v
        return self._name[v]

    def _st(self, name: str) -> str:
        return self.ctx.st(name)

    def _put_device(self, name: str, val: Any, from_host: bool = False) -> None:
        st = self._st(name)
        if not from_host:
            # a new value (an input, an op's output, an in-place update):
            # a host copy holds an older one and may not restore it
            self._drop_host(st)
        self._writes[st] = self._writes.get(st, 0) + 1
        if st in self.device:
            self.device[st] = val  # in-place overwrite (aliased update)
            return
        self.device[st] = val
        self.accountant.alloc(self.ctx.job_id, st,
                              self.ctx.sizes.get(st, _arr_bytes(val)))

    def _drop_device(self, name: str) -> None:
        self._drop_storage(self._st(name))

    def _drop_storage(self, st: str) -> None:
        if st in self.device:
            self.device.pop(st)
            self.accountant.free(self.ctx.job_id, st)

    def _get(self, name: str):
        return self.device.get(self._st(name))

    # ------------------------------------------------------------------
    def _host_put(self, st: str, val: Any, compressed: bool) -> None:
        self.host[st] = val
        self.ctx.host.add(st)
        if compressed:
            self.ctx.host_compressed.add(st)
        else:
            self.ctx.host_compressed.discard(st)

    def _drop_host(self, st: str) -> None:
        self.host.pop(st, None)
        self.ctx.host.discard(st)
        self.ctx.host_compressed.discard(st)

    def _host_fetch(self, st: str):
        """Materialize a device value from the host store (dequantizing a
        compressed copy through the Pallas kernel)."""
        val = self.host[st]
        if st in self.ctx.host_compressed:
            from repro.kernels.offload_quant import dequantize_blocked
            q, s, meta = val
            return dequantize_blocked(q, s, meta)
        return jax.numpy.asarray(val)

    def _swap_out(self, name: str, compressed: bool = False) -> None:
        st = self._st(name)
        if st not in self.device or st in self._pending_out:
            return
        # the copy empties the box: a finished swap-out, still referenced
        # by the swap thread until its next transfer, pins no device memory
        box = [self.device[st]]

        def do():
            val = box.pop()
            hub = self.telemetry
            ts = hub.now() if hub is not None else 0.0
            t0 = _time.perf_counter()
            if compressed:
                from repro.kernels.offload_quant import quantize_blocked
                self._host_put(st, quantize_blocked(jax.numpy.asarray(val)),
                               compressed=True)
            else:
                self._host_put(st, np.asarray(val), compressed=False)
            if hub is not None:
                hub.record_transfer(
                    self.ctx.job_id, st, "out", self.ctx.size_of(st),
                    _time.perf_counter() - t0, compressed=compressed, t=ts)

        written = self._writes.get(st, 0)
        if self.async_exec:
            # double-buffered stream: compute proceeds while the copy is
            # on the wire; the device copy is retired at the next poll
            # point, never before the copy lands (paper semantics kept —
            # the ledger free happens only after completion)
            done = self.async_exec.submit("out:" + st, do)
            self._pending_out[st] = (done, compressed, written)
            return
        self.channel.transfer(do)
        self._retire_out(st, compressed, written)

    def _retire_out(self, st: str, compressed: bool, written: int) -> None:
        """A swap-out's copy has landed: record, free the device copy,
        count.  If the storage took a new value while the old one was on
        the wire (an in-place update), the device keeps the new value and
        the host copy, now stale, is dropped."""
        if self._writes.get(st, 0) != written:
            self._drop_host(st)
            return
        self.engine.record("swap_out", self.ctx, st)
        self._drop_storage(st)
        self.stats.swap_out_count += 1
        if compressed:
            self.stats.compressed_swaps += 1

    def _poll_swap_outs(self, block: bool = False) -> None:
        """Non-blocking completion poll of in-flight swap-outs (the other
        half of the double buffer): retire every copy that has landed.
        With ``block=True`` wait for all of them (drain / safe points)."""
        for st, (done, compressed, written) in list(
                self._pending_out.items()):
            if block:
                done.wait()
            if done.is_set():
                del self._pending_out[st]
                self._retire_out(st, compressed, written)

    def _swap_in(self, name: str, passive: bool) -> bool:
        """Prefetch from host; returns False when there is nothing to fetch
        (e.g. iteration-0 cold start of a cross-iteration plan)."""
        st = self._st(name)
        if st in self.device:
            return True
        if st not in self.host:
            return False
        compressed = st in self.ctx.host_compressed

        def do():
            hub = self.telemetry
            ts = hub.now() if hub is not None else 0.0
            t0 = _time.perf_counter()
            self._put_device(st, self._host_fetch(st), from_host=True)
            if hub is not None:
                hub.record_transfer(
                    self.ctx.job_id, st, "in", self.ctx.size_of(st),
                    _time.perf_counter() - t0, compressed=compressed,
                    passive=passive, t=ts)

        self.engine.record("passive_in" if passive else "swap_in",
                           self.ctx, st)
        if self.async_exec and not passive:
            self.async_exec.submit("in:" + st, do)
        else:
            t0 = _time.perf_counter()
            self.channel.transfer(do)
            if passive:
                self.stats.passive_swap_ins += 1
                stall = _time.perf_counter() - t0
                self.stats.stall_time_s += stall
                if self.telemetry is not None:
                    self.telemetry.record_stall(
                        self.ctx.job_id, self._cur_idx, stall, "passive_in")
        self.stats.swap_in_count += 1
        return True

    def _ensure_input(self, name: str) -> None:
        """An operator needs `name` now: prefetch-wait, passive swap-in, or
        recompute from the producer equation (engine decision rules)."""
        st = self._st(name)
        inflight = bool(self.async_exec
                        and ("in:" + st) in self.async_exec.inflight)
        action = self.ctx.input_action(self.resident, name,
                                       prefetch_inflight=inflight)
        if action is INPUT_RESIDENT:
            return
        if action is INPUT_AWAIT_PREFETCH:
            ts = _time.perf_counter()
            self.async_exec.inflight["in:" + st].wait()
            stall = _time.perf_counter() - ts
            self.stats.stall_time_s += stall
            if self.telemetry is not None:
                self.telemetry.record_stall(
                    self.ctx.job_id, self._cur_idx, stall, "await_prefetch")
            if st in self.device:
                return
            action = self.ctx.input_action(self.resident, name)
        if action is INPUT_PASSIVE_SWAP_IN and self._swap_in(st, passive=True):
            return
        self._recompute(name)

    def _recompute(self, name: str) -> None:
        eqn_idx = self.producer.get(name)
        if eqn_idx is None:
            raise KeyError(f"tensor {name} unavailable and has no producer")
        eqn = self.jaxpr.eqns[eqn_idx]
        invals = []
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                invals.append(v.val)
                continue
            nm = self._name_of(v)
            self._ensure_input(nm)
            invals.append(self._get(nm))
        outs = _eval_eqn(eqn, invals)
        for v, o in zip(eqn.outvars, outs):
            if not _is_dropvar(v):
                self._put_device(self._name_of(v), o)
        self.stats.recompute_count += 1

    # ------------------------------------------------------------------
    def run(self, *args: Any) -> Any:
        return self.run_flat(jax.tree.leaves(args))

    def run_flat(self, flat: List[Any]) -> Any:
        """Run one iteration on the flattened inputs.  The executor takes
        ownership: ``flat`` is emptied once its values are placed, so a
        caller that keeps no other reference lets a swap-out or release
        free the device memory the plan says it frees."""
        t_start = _time.perf_counter()
        res_start = 0
        if self.telemetry is not None:
            res_start = len(
                self.telemetry.residency.get(self.ctx.job_id, ()))
        # absorb host values preloaded by the controller between iterations
        self.ctx.host |= set(self.host)
        assert len(flat) == len(self.jaxpr.invars), \
            f"expected {len(self.jaxpr.invars)} leaves, got {len(flat)}"
        for v, val in zip(self.jaxpr.invars, flat):
            nm = self._name_of(v)
            st = self._st(nm)
            if st in self.host_resident_inputs:
                # previous iteration parked this storage on host; it enters
                # the device only via its planned swap-in (or passively)
                self._host_put(st, np.asarray(val), compressed=False)
            else:
                self._put_device(nm, val)
        flat.clear()
        val = None  # nor may the loop variable pin the last input
        for v, val in zip(self.jaxpr.constvars, self.closed.consts):
            self._put_device(self._name_of(v), val)

        measure = self.measure_latency or self.telemetry is not None
        if self.telemetry is not None:
            # hot path: telemetry appends go through a per-thread buffer
            # flushed once per op boundary (one lock round-trip per op
            # instead of one per record)
            self.telemetry.begin_buffering()
        for idx, eqn in enumerate(self.jaxpr.eqns):
            self._cur_idx = idx
            # retire any swap-out whose copy landed while we computed
            self._poll_swap_outs()
            t0 = _time.perf_counter()
            invals = []
            for v in eqn.invars:
                if isinstance(v, jcore.Literal):
                    invals.append(v.val)
                    continue
                nm = self._name_of(v)
                self._ensure_input(nm)
                invals.append(self._get(nm))
            t1 = _time.perf_counter()
            outs = _eval_eqn(eqn, invals)
            if measure:
                jax.block_until_ready(outs)
                t2 = _time.perf_counter()
                if self.measure_latency:
                    self.stats.op_latencies.append(t2 - t0)
                if self.telemetry is not None:
                    # compute-only latency: input-ensure time is reported
                    # separately as stall records, so calibration samples
                    # are not polluted by memory waits
                    op = (self.seq.operators[idx]
                          if idx < len(self.seq.operators) else None)
                    self.telemetry.record_op(
                        self.ctx.job_id, idx, t2 - t1,
                        prim=eqn.primitive.name,
                        flops=op.flops if op else 0.0,
                        bytes_accessed=op.bytes_accessed if op else 0.0)
            for v, o in zip(eqn.outvars, outs):
                # dropped results still occupy their buffer until the op's
                # releases run — the allocator model both runtimes share
                self._put_device(self._name_of(v), o)

            # releases: plan overrides, then free-at-last-use (engine rule)
            for v in list(eqn.invars) + list(eqn.outvars):
                if isinstance(v, jcore.Literal):
                    continue
                nm = self._name_of(v)
                if self.ctx.should_auto_release(nm, idx):
                    self.engine.record("release", self.ctx, self._st(nm))
                    self._drop_device(nm)

            # plan events triggered by this op (engine skip rules)
            for ev in self.ctx.events_triggered_by(idx):
                st = self._st(ev.tensor_id)
                if not self.ctx.event_applies(self.resident, ev):
                    continue
                if ev.event_type is EventType.SWAP_OUT:
                    self._swap_out(ev.tensor_id, compressed=ev.compressed)
                elif ev.event_type is EventType.SWAP_IN:
                    self._swap_in(ev.tensor_id, passive=False)
                elif ev.event_type is EventType.RELEASE:
                    self.engine.record("release", self.ctx, st)
                    self._drop_device(ev.tensor_id)
                elif ev.event_type is EventType.RECOMPUTE:
                    self.engine.record("recompute", self.ctx, st)
                    self._recompute(ev.tensor_id)

            # preemptive arbitration: splice a pending plan in at a safe
            # point (after this op's events, before the next op)
            self._maybe_hot_swap(idx)
            if self.telemetry is not None:
                self.telemetry.flush()

        if self.async_exec:
            self.async_exec.drain()
        self._poll_swap_outs(block=True)
        if self.telemetry is not None:
            self.telemetry.end_buffering()
        # fetching outputs back to Python is harness work, not part of the
        # modeled iteration (steady state leaves swapped outputs on host) —
        # pause the trace (and telemetry) for it, resume afterwards
        if self.engine.trace is not None:
            self.engine.trace.paused = True
        if self.telemetry is not None:
            self.telemetry.paused = True
        outs = []
        for v in self.jaxpr.outvars:
            if isinstance(v, jcore.Literal):
                outs.append(v.val)
                continue
            nm = self._name_of(v)
            if self._get(nm) is None:
                self._ensure_input(nm)
            outs.append(self._get(nm))
        if self.engine.trace is not None:
            self.engine.trace.paused = False
        if self.telemetry is not None:
            self.telemetry.paused = False
            self.stats.residency_timeline = [
                (r.t, r.resident_bytes)
                for r in self.telemetry.residency.get(
                    self.ctx.job_id, [])[res_start:]]
            self.telemetry.end_iteration(self.ctx.job_id)
        self.stats.wall_time_s = _time.perf_counter() - t_start
        self.stats.peak_bytes = self.accountant.peak
        return outs

    # ------------------------------------------------------------------
    def ending_host_storages(self) -> Set[str]:
        """Storages left parked on host at iteration end (their device copy
        dropped) — the next iteration's `host_resident_inputs`."""
        return {st for st in self.host if st not in self.device}

    def close(self):
        if self.async_exec:
            self.async_exec.stop()


def _arr_bytes(x) -> int:
    try:
        return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
    except Exception:
        return 0


def _eval_eqn(eqn, invals: List[Any]) -> List[Any]:
    """Evaluate one jaxpr equation by binding its primitive.  That covers
    the call-like ones too: ``jit`` (a nested ``jax.jit``) runs its
    compiled sub-program, ``remat2`` (a ``jax.checkpoint`` region)
    evaluates its body.  Custom-derivative calls bind rule functions, not
    their equation params, so their primal body (``call_jaxpr``) runs
    instead."""
    prim = eqn.primitive
    if prim.name in ("custom_jvp_call", "custom_vjp_call"):
        return list(jcore.jaxpr_as_fun(eqn.params["call_jaxpr"])(*invals))
    outs = prim.bind(*invals, **eqn.params)
    if not prim.multiple_results:
        outs = [outs]
    return list(outs)


def reference_outputs(closed_jaxpr, *args: Any) -> List[Any]:
    flat, _ = jax.tree.flatten(args)
    return list(jcore.jaxpr_as_fun(closed_jaxpr)(*flat))
