"""Flash attention forward — Pallas TPU kernel.

Tiled online-softmax attention with GQA: the (S×S) score tensor — the
dominant memory-peak tensor TENSILE would otherwise swap — is never
materialized; only (block_q × block_k) tiles live in VMEM.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the kv axis is the
innermost (sequential) dimension, carrying running max / denominator /
accumulator in VMEM scratch (the standard TPU flash pattern).  Blocks are
MXU-aligned (128) by default.  Causal blocks that are fully masked
contribute nothing (the `pl.when` guard skips their FLOPs on TPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_mode

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int,
                  num_kv_blocks: int, seq_len_q: int, seq_len_kv: int,
                  sliding_window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip blocks that the causal mask voids entirely (saves their FLOPs)
    should_run = (k_start < q_start + block_q) if causal else (ki >= 0)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q * sm_scale, k,
                                (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_len_kv
        if causal:
            mask &= kpos <= qpos
        if sliding_window:
            mask &= kpos > qpos - sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v)
        m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0, 0, ...] = (acc_scr[...]
                            / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        sliding_window: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: Optional[bool] = None):
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H % KV == 0.
    Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    group = h // kvh
    sm_scale = 1.0 / np.sqrt(d)

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(skv, 8))
    sq_pad = -sq % block_q
    skv_pad = -skv % block_k
    qt = jnp.moveaxis(q, 2, 1)                       # (B,H,Sq,D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if sq_pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
    if skv_pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, skv_pad), (0, 0)))
    nq = (sq + sq_pad) // block_q
    nk = (skv + skv_pad) // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv_blocks=nk, seq_len_q=sq, seq_len_kv=skv,
        sliding_window=sliding_window)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret_mode(q, interpret),
        name="flash_attention_fwd",
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :sq], 1, 2)
