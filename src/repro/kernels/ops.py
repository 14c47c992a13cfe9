"""Jit'd public wrappers for the Pallas kernels.

Every kernel resolves its ``interpret`` flag through
``kernels.backend.interpret_mode``: compiled to Mosaic on a TPU, run in
interpret mode (the kernel body in Python — the correctness path) on the
CPU test suite.
"""
from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention_fwd
from .ssd_scan import ssd_intra_chunk_fwd


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, sliding_window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    return flash_attention_fwd(q, k, v, causal=causal,
                               sliding_window=sliding_window,
                               block_q=block_q, block_k=block_k)


@jax.jit
def ssd_intra_chunk(xc, dtc, da, bc, cc):
    return ssd_intra_chunk_fwd(xc, dtc, da, bc, cc)
