"""Pieces every plain reference shares: matrix products at a stated
precision, RMSNorm, cross-entropy, seeded initialisation and AdamW."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x):
    """Round ``x`` to float8 e4m3 with one scale for the whole tensor.  The
    rounding passes gradients through unchanged (straight-through), so the
    backward pass differentiates the float8 forward."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(FP8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def make_dot(precision: str):
    """``einsum`` at the named precision.  ``"float32"`` is the reference
    (full float32 products on any backend); ``"fp8"`` rounds both operands
    to float8 e4m3 first: the control, one step below bfloat16."""
    if precision == "float32":
        def dot(spec, a, b):
            return jnp.einsum(spec, a.astype(jnp.float32),
                              b.astype(jnp.float32), precision=HIGHEST)
    elif precision == "fp8":
        def dot(spec, a, b):
            return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return dot


def rmsnorm(x, scale, eps):
    """RMSNorm with the program's (1 + scale) gain."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def cross_entropy(logits, labels):
    """Mean over every position of logsumexp - the label's logit."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def padded_vocab(vocab: int) -> int:
    """Rows of the program's embedding table: the vocabulary rounded up
    to a multiple of 256."""
    return -(-vocab // 256) * 256


class Init:
    """Seeded initialiser: each call takes the next key."""

    def __init__(self, key, dtype):
        self.key = key
        self.dtype = dtype

    def _next(self):
        self.key, k = jax.random.split(self.key)
        return k

    def normal(self, shape, scale):
        return (jax.random.normal(self._next(), shape, jnp.float32)
                * scale).astype(self.dtype)

    def dense(self, shape, fan_in):
        """N(0, 1/fan_in)."""
        return self.normal(shape, 1.0 / np.sqrt(fan_in))

    def uniform(self, shape, lo, hi):
        return jax.random.uniform(self._next(), shape, jnp.float32, lo,
                                  hi).astype(self.dtype)

    def const(self, shape, value):
        return jnp.full(shape, value, self.dtype)


def adamw_step(params, grads, mu, nu, step: int, *, lr: float,
               b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """One AdamW update (no weight decay) of one leaf, in float32."""
    mu = b1 * mu + (1.0 - b1) * grads
    nu = b2 * nu + (1.0 - b2) * grads * grads
    mhat = mu / (1.0 - b1 ** step)
    vhat = nu / (1.0 - b2 ** step)
    return params - lr * (mhat / (jnp.sqrt(vhat) + eps)), mu, nu
