"""Compressed-swap kernels — beyond-paper optimization (DESIGN.md §2).

TENSILE's bottleneck is the host link: one transfer at a time at ~16 GB/s.
Quantizing swapped tensors to int8 with per-block scales halves (bf16) or
quarters (fp32) the bytes the channel must carry; the error affects only
the offloaded copy (activations destined for the backward pass tolerate
int8 well — gradient checkpointing literature routinely stores fp8/int8).

`quantize_blocked` / `dequantize_blocked` are Pallas kernels over the
tensor flattened to rows of BLOCK elements: each row gets one scale
(absmax/127) and packs to int8.  A grid step handles ``TILE_ROWS`` rows
(a multiple of 8, or all rows of a small tensor), so the TPU's (8, 128)
tiling holds; the per-row scale — what ``MachineProfile.
offload_quant_ratio`` prices — is unchanged.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret_mode

BLOCK = 512
TILE_ROWS = 256


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)               # (TILE_ROWS, BLOCK)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(x_ref.dtype)


def _to_2d(x):
    n = x.size
    pad = -n % BLOCK
    flat = jnp.pad(x.reshape(-1), (0, pad))
    return flat.reshape(-1, BLOCK), pad


def quantize_blocked(x, *, interpret: Optional[bool] = None):
    """x: any shape/float dtype -> (q int8 (R,BLOCK), scales (R,1), meta)."""
    x2, pad = _to_2d(x)
    r = x2.shape[0]
    t = min(r, TILE_ROWS)   # a ragged last tile: padded reads, masked writes
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(r, t),),
        in_specs=[pl.BlockSpec((t, BLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((t, BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((t, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((r, 1), jnp.float32)],
        interpret=interpret_mode(x, interpret),
        name="quantize_blocked",
    )(x2)
    return q, s, (x.shape, str(x.dtype), pad)


def dequantize_blocked(q, s, meta, *, interpret: Optional[bool] = None):
    shape, dtype, pad = meta
    r = q.shape[0]
    t = min(r, TILE_ROWS)
    x2 = pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(r, t),),
        in_specs=[pl.BlockSpec((t, BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((t, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((t, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, BLOCK), jnp.dtype(dtype)),
        interpret=interpret_mode(q, interpret),
        name="dequantize_blocked",
    )(q, s)
    flat = x2.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)


def compression_ratio(dtype) -> float:
    """Achieved swap-byte ratio vs the uncompressed tensor (incl. scales)."""
    itemsize = jnp.dtype(dtype).itemsize
    return (1.0 + 4.0 / BLOCK) / itemsize
