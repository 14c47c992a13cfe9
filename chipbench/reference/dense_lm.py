"""Plain float32 reference of the program's dense decoder: GQA attention
with rotary positions and a squared-ReLU MLP, pre-norm, untied output head.

Follows the program's block, not only the published one (the configuration
file lists where the two differ): RMSNorm with a (1 + scale) gain, rotary
over the whole head in rotate-half form, and the embedding table rounded up
to a multiple of 256 rows.  Attention is plain softmax attention, one KV
head group at a time so that the (S x S) scores of one group are the
largest temporary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import Init, cross_entropy, padded_vocab, rmsnorm


def init_params(model: dict, key, dtype):
    """The program's parameter tree, made from ``key`` in ``dtype``."""
    L, d = model["n_layers"], model["d_model"]
    H, KV, K = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    F, V = model["d_ff"], padded_vocab(model["vocab_size"])
    ini = Init(key, dtype)
    embed = {"tok": ini.normal((V, d), 1.0)}
    if not model.get("tie_embeddings", False):
        embed["head"] = ini.dense((d, V), d)
    layer = {
        "ln1": ini.const((L, d), 0.0),
        "attn": {"wq": ini.dense((L, d, H, K), d),
                 "wk": ini.dense((L, d, KV, K), d),
                 "wv": ini.dense((L, d, KV, K), d),
                 "wo": ini.dense((L, H, K, d), H * K)},
        "ln2": ini.const((L, d), 0.0),
        "mlp": {"wi": ini.dense((L, d, F), d),
                "wo": ini.dense((L, F, d), F)},
    }
    return {"embed": embed, "blocks": {"layer0": layer},
            "final_norm": ini.const((d,), 0.0)}


def _rope(x, theta):
    """Rotate-half rotary embedding of x: (B, S, heads, K)."""
    s, k = x.shape[1], x.shape[-1]
    half = k // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = np.arange(s, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, dot):
    """Causal softmax attention.  q: (B, S, H, K); k, v: (B, S, KV, K)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, d)
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def group(args):
        qi, ki, vi = args                    # (B,S,G,K), (B,S,K), (B,S,K)
        sc = dot("bqgk,bsk->bgqs", qi, ki) / np.sqrt(d)
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return dot("bgqs,bsk->bqgk", p, vi)

    out = jax.lax.map(group, (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0),
                              jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h, d)


def loss(params, tokens, labels, model: dict, dot):
    """Mean next-token cross-entropy, computed in float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps, theta = model["norm_eps"], model["rope_theta"]
    x = jnp.take(p["embed"]["tok"], tokens, axis=0)

    @jax.checkpoint
    def layer(x, lp):
        h = rmsnorm(x, lp["ln1"], eps)
        a = lp["attn"]
        q = _rope(dot("bsd,dhk->bshk", h, a["wq"]), theta)
        k = _rope(dot("bsd,dhk->bshk", h, a["wk"]), theta)
        v = dot("bsd,dhk->bshk", h, a["wv"])
        x = x + dot("bshk,hkd->bsd", _attention(q, k, v, dot), a["wo"])
        h = rmsnorm(x, lp["ln2"], eps)
        u = jnp.square(jax.nn.relu(dot("bsd,df->bsf", h, lp["mlp"]["wi"])))
        return x + dot("bsf,fd->bsd", u, lp["mlp"]["wo"]), None

    x, _ = jax.lax.scan(layer, x, p["blocks"]["layer0"])
    x = rmsnorm(x, p["final_norm"], eps)
    if "head" in p["embed"]:
        logits = dot("bsd,dv->bsv", x, p["embed"]["head"])
    else:
        logits = dot("bsd,vd->bsv", x, p["embed"]["tok"])
    return cross_entropy(logits, labels)


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Operations a training step needs per token: forward and backward
    (3x the forward), no recomputation.  Matrix products count 2 per
    multiply-add; causal attention counts the (S + 1) / 2 keys a query
    sees on average."""
    L, d = model["n_layers"], model["d_model"]
    H, KV, K = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    F, V = model["d_ff"], model["vocab_size"]
    per_layer = d * H * K + 2 * d * KV * K + H * K * d + 2 * d * F
    fwd = 2 * (L * per_layer + d * V)
    fwd += L * 2 * 2 * H * K * (seq_len + 1) / 2
    return 3.0 * fwd
