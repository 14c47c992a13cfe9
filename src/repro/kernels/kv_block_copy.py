"""Batched KV-block gather/scatter: one launch moves a whole block set.

The serving plane's batched data path (`ServingEngine` under
``batch_transfers``) moves the cohort's ``kv/<rid>/b<i>`` regions per
decode turn.  The per-slot path costs one device launch per (leaf, slot);
these kernels move the *set* in a single launch per cache leaf:

* ``kv_block_gather(pool, idx, axis)``   -> ``take(pool, idx, axis)``
* ``kv_block_scatter(pool, idx, blocks, axis)`` -> pool with the ``idx``
  slots along ``axis`` replaced by ``blocks`` (in place via
  ``input_output_aliases``)

The pool is viewed, without a copy, as ``(pre, slots, rows, lanes)``:
``pre`` is every axis before the slot axis (the layer axis of a cache
leaf), and one slot's trailing elements are folded into rows of
``LANES`` = 128 lanes.  The grid is ``(pre, len(idx), row tiles)`` and a
block is ``(1, 1, tile_rows, lanes)``: its last two dimensions are a
multiple of (8, 128) or the array's own, and it holds at most
``BLOCK_BYTES``, so a 23 MB slot row of a full-width cache leaf moves in
fast-memory-sized pieces.  Slot indices arrive through a scalar-prefetch
argument (``pltpu.PrefetchScalarGridSpec``), so the block index maps are
computed before the kernel body runs — the TPU-idiomatic dynamic gather.
Pure oracles live in ``kernels/ref.py``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_mode

LANES = 128
# bytes of one block; the pipeline double-buffers each operand, so a
# gather holds four blocks of fast memory at a time
BLOCK_BYTES = 1 << 20


def _view(shape, axis: int, itemsize: int):
    """``(pre, slots, rows, lanes)`` view of a pool with its slot axis at
    ``axis``, and the row tile of one block."""
    pre = math.prod(shape[:axis])
    n = shape[axis]
    per_slot = math.prod(shape[axis + 1:])
    lanes = LANES if per_slot % LANES == 0 else per_slot
    rows = per_slot // lanes
    max_rows = max(8, BLOCK_BYTES // (lanes * itemsize) // 8 * 8)
    if rows <= max_rows:
        return (pre, n, rows, lanes), rows
    # the largest multiple of 8 that divides the rows and fits a block;
    # a ragged last tile (reads padded, writes masked) only without one
    tile = next((t for t in range(max_rows, 7, -8) if rows % t == 0),
                max_rows)
    return (pre, n, rows, lanes), tile


def _copy_kernel(idx_ref, src_ref, out_ref):
    del idx_ref  # consumed by the index maps
    out_ref[...] = src_ref[...]


def kv_block_gather(pool, idx, *, axis: int = 0,
                    interpret: Optional[bool] = None):
    """Gather slots ``idx`` along ``axis`` of ``pool`` in one launch:
    returns ``pool``'s shape with ``len(idx)`` slots on ``axis``."""
    pool = jnp.asarray(pool)
    idx = jnp.asarray(idx, jnp.int32)
    k = idx.shape[0]
    view, tile = _view(pool.shape, axis, pool.dtype.itemsize)
    pre, _, rows, lanes = view
    block = (1, 1, tile, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pre, k, pl.cdiv(rows, tile)),
        in_specs=[pl.BlockSpec(block,
                               lambda p, i, j, idx_ref: (p, idx_ref[i], j, 0))],
        out_specs=pl.BlockSpec(block, lambda p, i, j, idx_ref: (p, i, j, 0)))
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((pre, k, rows, lanes), pool.dtype),
        interpret=interpret_mode(pool, interpret),
        name="kv_block_gather")(idx, pool.reshape(view))
    return out.reshape(pool.shape[:axis] + (k,) + pool.shape[axis + 1:])


def _scatter_kernel(idx_ref, blocks_ref, pool_ref, out_ref):
    del idx_ref, pool_ref  # index maps / aliased initial value
    out_ref[...] = blocks_ref[...]


def kv_block_scatter(pool, idx, blocks, *, axis: int = 0,
                     interpret: Optional[bool] = None):
    """Scatter ``blocks`` (``pool``'s shape with ``len(idx)`` slots on
    ``axis``) into slots ``idx`` of ``pool`` in one launch; slots not in
    ``idx`` keep their values (the pool buffer is aliased into the
    output and never read)."""
    pool = jnp.asarray(pool)
    idx = jnp.asarray(idx, jnp.int32)
    blocks = jnp.asarray(blocks, pool.dtype)
    k = idx.shape[0]
    view, tile = _view(pool.shape, axis, pool.dtype.itemsize)
    pre, _, rows, lanes = view
    block = (1, 1, tile, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pre, k, pl.cdiv(rows, tile)),
        in_specs=[
            pl.BlockSpec(block, lambda p, i, j, idx_ref: (p, i, j, 0)),
            pl.BlockSpec(memory_space=pl.ANY),                  # pool
        ],
        out_specs=pl.BlockSpec(block,
                               lambda p, i, j, idx_ref: (p, idx_ref[i], j, 0)))
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(view, pool.dtype),
        # the pool operand (arg 2: after the scalar idx and blocks) is
        # donated into the output, so unwritten slots pass through
        input_output_aliases={2: 0},
        interpret=interpret_mode(pool, interpret),
        name="kv_block_scatter")(idx, blocks.reshape((pre, k, rows, lanes)),
                                 pool.reshape(view))
    return out.reshape(pool.shape)
