"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        sliding_window: int = 0):
    """q: (B,Sq,H,D); k,v: (B,Skv,KV,D) -> (B,Sq,H,D); fp32 softmax."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    skv = k.shape[1]
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if sliding_window:
        mask &= kpos > qpos - sliding_window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _segsum(x):
    s = jnp.cumsum(x, axis=-1)
    diff = s[..., :, None] - s[..., None, :]
    q = x.shape[-1]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_intra_chunk_ref(xc, dtc, da, bc, cc):
    """xc: (B,NC,Q,H,P); dtc/da: (B,NC,Q,H); bc/cc: (B,NC,Q,N)
    -> y_diag (B,NC,Q,H,P) fp32, states (B,NC,H,P,N) fp32."""
    xc32 = xc.astype(jnp.float32)
    da32 = da.astype(jnp.float32)
    dt32 = dtc.astype(jnp.float32)
    b32 = bc.astype(jnp.float32)
    c32 = cc.astype(jnp.float32)
    lmat = jnp.exp(_segsum(jnp.moveaxis(da32, 2, 3)))        # (B,NC,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", c32, b32)
    y = jnp.einsum("bcqk,bchqk,bckh,bckhp->bcqhp", scores, lmat, dt32, xc32)
    cum = jnp.cumsum(da32, axis=2)
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum)
    states = jnp.einsum("bcqh,bcqh,bcqn,bcqhp->bchpn",
                        decay_end, dt32, b32, xc32)
    return y, states


def kv_block_gather_ref(pool, idx, axis: int = 0):
    """pool: slots on ``axis``; idx: (K,) int -> the K slots of the pool
    (pool's shape with K on ``axis``)."""
    return jnp.take(jnp.asarray(pool), jnp.asarray(idx, jnp.int32), axis=axis)


def kv_block_scatter_ref(pool, idx, blocks, axis: int = 0):
    """pool: slots on ``axis``; idx: (K,) int; blocks: K slots on ``axis``
    -> pool with the ``idx`` slots replaced by ``blocks``; all other slots
    untouched."""
    pool = jnp.moveaxis(jnp.asarray(pool), axis, 0)
    blocks = jnp.moveaxis(jnp.asarray(blocks, pool.dtype), axis, 0)
    out = pool.at[jnp.asarray(idx, jnp.int32)].set(blocks)
    return jnp.moveaxis(out, 0, axis)


def quantize_blocked_ref(x, block: int = 512):
    flat = np.asarray(x, np.float32).reshape(-1)
    pad = -flat.size % block
    flat = np.pad(flat, (0, pad))
    x2 = flat.reshape(-1, block)
    amax = np.abs(x2).max(axis=-1, keepdims=True)
    scale = np.maximum(amax, 1e-12) / 127.0
    q = np.clip(np.round(x2 / scale), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32), (x.shape, str(x.dtype), pad)


def dequantize_blocked_ref(q, s, meta):
    shape, dtype, pad = meta
    flat = (q.astype(np.float32) * s).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape).astype(dtype)
