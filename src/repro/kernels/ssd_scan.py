"""Mamba-2 SSD intra-chunk kernel — Pallas TPU.

Computes, per (batch, chunk, head) grid cell, the quadratic-dual intra-chunk
output and the chunk's contribution to the inter-chunk state:

    L[i,j]   = exp(sum_{j<k<=i} dA_k)          (lower-triangular decay)
    y_diag   = ((C Bᵀ) ⊙ L ⊙ dtᵀ) X            (Q,P)
    state    = Bᵀ ((exp(dA_total − cum(dA)) ⊙ dt) ⊙ X)   (N,P)

The (Q,Q) decay/score tiles live only in VMEM (Q = ssm_chunk, 256 default —
a 256×256 fp32 tile).  The cheap inter-chunk recurrence stays in jnp
(`models.ssm.ssd_chunked` consumes these outputs).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret_mode


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, st_ref):
    x = x_ref[0, 0, :, 0].astype(jnp.float32)        # (Q,P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)      # (Q,)
    da = da_ref[0, 0, :, 0].astype(jnp.float32)      # (Q,)
    bb = b_ref[0, 0].astype(jnp.float32)             # (Q,N)
    cc = c_ref[0, 0].astype(jnp.float32)             # (Q,N)

    cum = jnp.cumsum(da)                             # (Q,)
    diff = cum[:, None] - cum[None, :]               # (Q,Q)
    q = diff.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmat = jnp.where(ii >= jj, jnp.exp(diff), 0.0)   # (Q,Q)

    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())))  # (Q,Q)
    w = scores * lmat * dt[None, :]
    y = jax.lax.dot(w, x)                            # (Q,P)
    y_ref[0, 0, :, 0] = y.astype(y_ref.dtype)

    decay_end = jnp.exp(cum[-1] - cum) * dt          # (Q,)
    st = jax.lax.dot_general(bb * decay_end[:, None], x,
                             (((0,), (0,)), ((), ())))  # (N,P)
    st_ref[0, 0, 0] = st.astype(st_ref.dtype)


def ssd_intra_chunk_fwd(xc, dtc, da, bc, cc, *,
                        interpret: Optional[bool] = None):
    """xc: (B,NC,Q,H,P); dtc/da: (B,NC,Q,H); bc/cc: (B,NC,Q,N).

    Returns y_diag: (B,NC,Q,H,P), states: (B,NC,H,P,N) — matching the jnp
    reference in models.ssm / kernels.ref.
    """
    b, nc, q, h, p = xc.shape
    n = bc.shape[-1]
    kernel = _ssd_kernel

    y, st = pl.pallas_call(
        kernel,
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, q, 1, p),
                         lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, ci, hi: (bi, ci, 0, hi)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, ci, hi: (bi, ci, 0, hi)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, 1, p),
                         lambda bi, ci, hi: (bi, ci, 0, hi, 0)),
            pl.BlockSpec((1, 1, 1, n, p),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, q, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, n, p), jnp.float32),
        ],
        interpret=interpret_mode(xc, interpret),
    )(xc, dtc, da, bc, cc)
    # states stored (N,P) per head -> transpose to (P,N)
    return y, jnp.swapaxes(st, -1, -2)
