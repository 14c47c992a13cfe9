"""PR-9 runtime data path: DMA coalescing, the double-buffered async
swap stream, buffered telemetry, and the batched KV-block kernels."""
import threading

import numpy as np
import pytest

from repro.core.engine import DmaChannel
from repro.core.executor import AsyncSwapExecutor
from repro.core.telemetry import TelemetryHub, record_schemas

FIX = 15e-6      # per-transfer fixup (setup) latency
OVER = 2e-6      # per-extra-member batch overhead


# ----------------------------------------------------------------------
# DmaChannel coalescing (virtual time)
# ----------------------------------------------------------------------
class TestDmaCoalescing:
    def test_off_by_default_bookings_identical(self):
        plain, tagged = DmaChannel(), DmaChannel()
        slots_plain, slots_tagged = [], []
        t = 0.0
        for dur in (3e-4, 1e-4, 2e-4):
            slots_plain.append(plain.acquire(t, dur))
            # direction/fixup tags must be inert while coalesce=False
            slots_tagged.append(tagged.acquire(t, dur, direction="in",
                                               fixup=FIX))
            t = slots_plain[-1][1]
        assert slots_plain == slots_tagged
        assert tagged.batched_transfers == 0
        assert tagged.coalesced_bookings == 0
        assert tagged.saved_fixup_s == 0.0
        assert tagged.busy_until == plain.busy_until

    def test_adjacent_same_direction_merge_pays_one_fixup(self):
        ch = DmaChannel(coalesce=True, coalesce_window=1e-3,
                        batch_overhead_s=OVER)
        d0, d1 = 3e-4, 2e-4
        s0, e0 = ch.acquire(0.0, FIX + d0, direction="in", fixup=FIX)
        assert (s0, e0) == (0.0, FIX + d0)
        # second booking lands at the tail within the window: it merges,
        # paying its payload + batch overhead instead of another fixup
        s1, e1 = ch.acquire(e0, FIX + d1, direction="in", fixup=FIX)
        assert s1 == e0
        assert e1 == pytest.approx(e0 + d1 + OVER)
        assert ch.busy_until == pytest.approx(e1)
        assert ch.batched_transfers == 1
        assert ch.coalesced_bookings == 2     # opener + merged member
        assert ch.saved_fixup_s == pytest.approx(FIX - OVER)

    def test_direction_change_breaks_the_batch(self):
        ch = DmaChannel(coalesce=True, coalesce_window=1e-3,
                        batch_overhead_s=OVER)
        _, e0 = ch.acquire(0.0, FIX + 3e-4, direction="out", fixup=FIX)
        s1, e1 = ch.acquire(e0, FIX + 2e-4, direction="in", fixup=FIX)
        # opposite direction: a fresh full-cost slot, nothing coalesced
        assert (s1, e1) == (e0, e0 + FIX + 2e-4)
        assert ch.batched_transfers == 0
        assert ch.saved_fixup_s == 0.0

    def test_gap_beyond_window_breaks_the_batch(self):
        ch = DmaChannel(coalesce=True, coalesce_window=1e-5,
                        batch_overhead_s=OVER)
        _, e0 = ch.acquire(0.0, FIX + 3e-4, direction="in", fixup=FIX)
        late = e0 + 5e-4   # well past the window
        s1, e1 = ch.acquire(late, FIX + 2e-4, direction="in", fixup=FIX)
        assert (s1, e1) == (late, late + FIX + 2e-4)
        assert ch.batched_transfers == 0

    def test_merged_tail_refund_restores_the_batch_end(self):
        ch = DmaChannel(coalesce=True, coalesce_window=1e-3,
                        batch_overhead_s=OVER)
        _, e0 = ch.acquire(0.0, FIX + 3e-4, direction="in", fixup=FIX)
        s1, e1 = ch.acquire(e0, FIX + 2e-4, direction="in", fixup=FIX)
        assert ch.try_refund(s1, e1)
        assert ch.busy_until == pytest.approx(e0)

    def test_acquire_batch_matches_sequential_merges(self):
        durs = [3e-4, 2e-4, 1e-4]
        # booking the cohort explicitly ...
        batch = DmaChannel(coalesce=True, batch_overhead_s=OVER)
        s, e = batch.acquire_batch(0.0, durs, fixup=FIX, direction="in")
        assert (s, e) == (0.0, pytest.approx(FIX + sum(durs)
                                             + OVER * (len(durs) - 1)))
        assert batch.batched_transfers == 1
        assert batch.coalesced_bookings == len(durs)
        assert batch.saved_fixup_s == pytest.approx(
            (FIX - OVER) * (len(durs) - 1))
        # ... costs exactly what back-to-back window merges cost
        seq = DmaChannel(coalesce=True, coalesce_window=1e-3,
                         batch_overhead_s=OVER)
        t = 0.0
        for d in durs:
            _, t = seq.acquire(t, FIX + d, direction="in", fixup=FIX)
        assert t == pytest.approx(e)
        assert seq.saved_fixup_s == pytest.approx(batch.saved_fixup_s)

    def test_acquire_batch_degenerate_sizes(self):
        ch = DmaChannel(coalesce=True, batch_overhead_s=OVER)
        assert ch.acquire_batch(1.0, [], fixup=FIX) == (1.0, 1.0)
        s, e = ch.acquire_batch(1.0, [2e-4], fixup=FIX, direction="out")
        assert (s, e) == (1.0, 1.0 + FIX + 2e-4)  # single == plain acquire
        assert ch.batched_transfers == 0


# ----------------------------------------------------------------------
# AsyncSwapExecutor: queued same-direction transfers share one launch
# ----------------------------------------------------------------------
def test_queued_prefetches_coalesce_into_one_launch():
    ch = DmaChannel()
    ex = AsyncSwapExecutor(ch)
    try:
        started, gate = threading.Event(), threading.Event()

        def slow_out():
            started.set()
            gate.wait(5.0)

        ex.submit("out:x", slow_out)
        assert started.wait(5.0)
        # while the swap-out occupies the worker, two prefetches queue up
        done_a = ex.submit("in:a", lambda: None)
        done_b = ex.submit("in:b", lambda: None)
        gate.set()
        assert done_a.wait(5.0) and done_b.wait(5.0)
        ex.drain()
        # regression: both queued prefetches ride ONE transfer_batch launch
        assert ["in:a", "in:b"] in ex.batches
        assert ch.batched_transfers == 1
        assert ch.coalesced_bookings == 2
    finally:
        ex.stop()


def test_direction_change_defers_to_the_next_launch():
    ch = DmaChannel()
    ex = AsyncSwapExecutor(ch)
    try:
        started, gate = threading.Event(), threading.Event()

        def slow_out():
            started.set()
            gate.wait(5.0)

        ex.submit("out:x", slow_out)
        assert started.wait(5.0)
        evs = [ex.submit("in:a", lambda: None),
               ex.submit("out:y", lambda: None),
               ex.submit("in:b", lambda: None)]
        gate.set()
        for ev in evs:
            assert ev.wait(5.0)
        ex.drain()
        # FIFO order across the direction change is preserved: the "out"
        # item breaks the in-batch, so in:a and in:b cannot share a launch
        flat = [k for b in ex.batches for k in b]
        assert flat == ["out:x", "in:a", "out:y", "in:b"]
        assert all(len(b) == 1 for b in ex.batches)
    finally:
        ex.stop()


def test_stop_lets_go_of_the_last_transfer():
    """After stop() no transfer closure is left on the worker: what it
    captured (an executor's value store, and through it device arrays)
    is freed as soon as its owner lets go of it."""
    import weakref

    class Store:
        pass

    ex = AsyncSwapExecutor(DmaChannel())
    store = Store()
    ref = weakref.ref(store)
    ex.submit("out:x", lambda s=store: s)
    del store
    ex.stop()
    assert not ex.thread.is_alive()
    assert ref() is None


# ----------------------------------------------------------------------
# TelemetryHub per-thread buffering
# ----------------------------------------------------------------------
def _emit(hub: TelemetryHub) -> None:
    hub.record_op("j", 0, 1e-3, prim="dot", flops=10.0, t=0.1)
    hub.record_transfer("j", "s0", "out", 1024, 2e-3, t=0.2)
    hub.record_stall("j", 1, 5e-4, "passive_in", t=0.3)
    hub.record_residency("j", "s0", "free", 0, t=0.4)
    hub.record_op("j", 1, 2e-3, prim="add", t=0.5)


def test_buffered_telemetry_identical_to_unbuffered():
    direct = TelemetryHub(clock="virtual")
    _emit(direct)
    buffered = TelemetryHub(clock="virtual")
    buffered.begin_buffering()
    _emit(buffered)
    # nothing published until the op-boundary flush ...
    assert buffered.ops.get("j") is None
    buffered.end_buffering()
    # ... then streams, order and record content match the direct path
    assert buffered.ops == direct.ops
    assert buffered.transfers == direct.transfers
    assert buffered.stalls == direct.stalls
    assert buffered.residency == direct.residency
    # the EWMA fold happens at publish time and matches too
    assert buffered._ewma == direct._ewma


def test_record_schemas_are_pinned():
    assert record_schemas() == {
        "op": ("job_id", "iteration", "op_idx", "prim", "latency_s",
               "flops", "bytes_accessed", "t", "cold"),
        "transfer": ("job_id", "iteration", "storage", "direction",
                     "size_bytes", "duration_s", "compressed", "passive",
                     "t"),
        "stall": ("job_id", "iteration", "op_idx", "cause", "duration_s",
                  "t"),
        "residency": ("job_id", "iteration", "storage", "action",
                      "resident_bytes", "t"),
    }


# ----------------------------------------------------------------------
# Batched KV-block kernels vs the jnp oracles
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def kv_pool():
    rng = np.random.default_rng(7)
    pool = rng.standard_normal((16, 256)).astype(np.float32)
    return pool, rng


def test_kv_block_gather_matches_ref(kv_pool):
    from repro.kernels.kv_block_copy import kv_block_gather
    from repro.kernels.ref import kv_block_gather_ref

    pool, rng = kv_pool
    for k in (1, 3, 7):
        idx = np.asarray(rng.permutation(pool.shape[0])[:k], np.int32)
        got = np.asarray(kv_block_gather(pool, idx))
        want = np.asarray(kv_block_gather_ref(pool, idx))
        np.testing.assert_array_equal(got, want)


def test_kv_block_scatter_matches_ref(kv_pool):
    from repro.kernels.kv_block_copy import kv_block_scatter
    from repro.kernels.ref import kv_block_scatter_ref

    pool, rng = kv_pool
    for k in (1, 4):
        idx = np.asarray(rng.permutation(pool.shape[0])[:k], np.int32)
        blocks = rng.standard_normal((k, pool.shape[1])).astype(np.float32)
        got = np.asarray(kv_block_scatter(pool, idx, blocks))
        want = np.asarray(kv_block_scatter_ref(pool, idx, blocks))
        np.testing.assert_array_equal(got, want)
        # rows outside idx pass through bit-identically
        untouched = np.setdiff1d(np.arange(pool.shape[0]), idx)
        np.testing.assert_array_equal(got[untouched], pool[untouched])


def test_kv_gather_scatter_roundtrip_is_identity(kv_pool):
    from repro.kernels.kv_block_copy import kv_block_gather, kv_block_scatter

    pool, rng = kv_pool
    idx = np.asarray(rng.permutation(pool.shape[0])[:5], np.int32)
    rows = kv_block_gather(pool, idx)
    back = np.asarray(kv_block_scatter(pool, idx, rows))
    np.testing.assert_array_equal(back, pool)


# ----------------------------------------------------------------------
# The (pre, slots, rows, lanes) block layout: the slot axis in place, as
# the serving engine passes a cache leaf, and rows tiled past one block
# ----------------------------------------------------------------------
@pytest.mark.parametrize("shape,axis", [
    ((3, 6, 40, 4, 64), 1),     # cache leaf: (layers, slots, len, kv, hd)
    ((2, 5, 7, 3), 1),          # a slot row that is not a lane multiple
    ((6, 4, 4096, 128), 0),     # 2 MiB slot rows: several row tiles
    ((2, 3, 4, 5), 3),          # slots on the last axis
])
def test_kv_block_copy_slot_axis_matches_ref(shape, axis):
    from repro.kernels.kv_block_copy import kv_block_gather, kv_block_scatter
    from repro.kernels.ref import kv_block_gather_ref, kv_block_scatter_ref

    rng = np.random.default_rng(11)
    pool = rng.standard_normal(shape).astype(np.float32)
    n = shape[axis]
    idx = np.asarray(rng.permutation(n)[:min(3, n)], np.int32)
    got = np.asarray(kv_block_gather(pool, idx, axis=axis))
    np.testing.assert_array_equal(
        got, np.asarray(kv_block_gather_ref(pool, idx, axis)))
    blocks = rng.standard_normal(got.shape).astype(np.float32)
    out = np.asarray(kv_block_scatter(pool, idx, blocks, axis=axis))
    np.testing.assert_array_equal(
        out, np.asarray(kv_block_scatter_ref(pool, idx, blocks, axis)))
    untouched = np.setdiff1d(np.arange(n), idx)
    np.testing.assert_array_equal(np.take(out, untouched, axis=axis),
                                  np.take(pool, untouched, axis=axis))


def test_kv_block_view_tiles_fit_fast_memory():
    """A full-width TinyLlama cache leaf (a 23 MB slot row) is cut into
    (8, 128)-aligned row tiles of at most BLOCK_BYTES each."""
    from repro.kernels.kv_block_copy import BLOCK_BYTES, LANES, _view

    view, tile = _view((22, 8, 2048, 4, 64), 1, 2)
    assert view == (22, 8, 2048 * 4 * 64 // LANES, LANES)
    assert tile % 8 == 0 and view[2] % tile == 0
    assert tile * LANES * 2 <= BLOCK_BYTES
