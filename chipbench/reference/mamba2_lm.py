"""Plain float32 reference of the program's Mamba-2 language model: an
embedding, pre-norm Mamba-2 mixers with no MLP, a final norm and the tied
output head.

The state-space part is the quadratic (dual) form of SSD over the whole
sequence, not the chunked scan the program runs:

    y[t] = sum_{s <= t} (C[t] . B[s]) exp(sum_{s < r <= t} dt[r] a) dt[s] x[s]
           + D x[t]

computed a group of heads at a time, so one group's (S x S) decay matrix is
the largest temporary.  It follows the program where the configuration
file says it departs from mamba_ssm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import Init, cross_entropy, padded_vocab, rmsnorm

HEAD_GROUP = 8


def _dims(model: dict):
    d = model["d_model"]
    dinner = model["ssm_expand"] * d
    return d, dinner, dinner // model["ssm_head_dim"], model["ssm_state"]


def init_params(model: dict, key, dtype):
    """The program's parameter tree, made from ``key`` in ``dtype``."""
    L = model["n_layers"]
    d, di, H, N = _dims(model)
    W = model["ssm_conv_width"]
    V = padded_vocab(model["vocab_size"])
    ini = Init(key, dtype)
    embed = {"tok": ini.normal((V, d), 1.0)}
    if not model.get("tie_embeddings", False):
        embed["head"] = ini.dense((d, V), d)
    # dt in [1e-3, 1e-1] log-uniformly, stored through softplus's inverse
    dt = jnp.exp(ini.uniform((L, H), np.log(1e-3), np.log(1e-1))
                 .astype(jnp.float32))
    mamba = {
        "wz": ini.dense((L, d, di), d), "wx": ini.dense((L, d, di), d),
        "wb": ini.dense((L, d, N), d), "wc": ini.dense((L, d, N), d),
        "wdt": ini.dense((L, d, H), d),
        "conv_wx": ini.uniform((L, W, di), -0.5, 0.5),
        "conv_bx": ini.const((L, di), 0.0),
        "conv_wb": ini.uniform((L, W, N), -0.5, 0.5),
        "conv_bb": ini.const((L, N), 0.0),
        "conv_wc": ini.uniform((L, W, N), -0.5, 0.5),
        "conv_bc": ini.const((L, N), 0.0),
        "a_log": jnp.log(ini.uniform((L, H), 1.0, 16.0)
                         .astype(jnp.float32)).astype(dtype),
        "d_skip": ini.const((L, H), 1.0),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "norm_scale": ini.const((L, di), 0.0),
        "out_proj": ini.dense((L, di, d), di),
    }
    return {"embed": embed,
            "blocks": {"layer0": {"ln1": ini.const((L, d), 0.0),
                                  "mamba": mamba}},
            "final_norm": ini.const((d,), 0.0)}


def _conv(u, w, b):
    """Causal depthwise convolution then SiLU.  u: (B, S, C); w: (W, C)."""
    width, s = w.shape[0], u.shape[1]
    full = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(full[:, i:i + s] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def _ssd(x, dt, a, bb, cc, dot):
    """x: (B, S, H, P); dt: (B, S, H); a: (H,); bb, cc: (B, S, N)."""
    b, s, h, p = x.shape
    cum = jnp.cumsum(dt * a, axis=1)                      # (B, S, H)
    cb = dot("btn,bsn->bts", cc, bb)                      # (B, S, S)
    mask = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    g = h // HEAD_GROUP

    def split(z):                                          # heads to front
        return jnp.moveaxis(z.reshape(*z.shape[:2], g, HEAD_GROUP,
                                      *z.shape[3:]), 2, 0)

    @jax.checkpoint
    def group(args):
        cum_g, dt_g, x_g = args           # (B,S,G'), (B,S,G'), (B,S,G',P)
        seg = jnp.where(mask, cum_g[:, :, None] - cum_g[:, None], -jnp.inf)
        w = cb[..., None] * jnp.exp(seg) * dt_g[:, None]  # (B, T, S, G')
        return dot("btsh,bshp->bthp", w, x_g)

    y = jax.lax.map(group, (split(cum), split(dt), split(x)))
    return jnp.moveaxis(y, 0, 2).reshape(b, s, h, p)


def loss(params, tokens, labels, model: dict, dot):
    """Mean next-token cross-entropy, computed in float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps = model["norm_eps"]
    _, di, H, _ = _dims(model)
    P = model["ssm_head_dim"]
    x = jnp.take(p["embed"]["tok"], tokens, axis=0)

    @jax.checkpoint
    def layer(x, lp):
        m = lp["mamba"]
        h = rmsnorm(x, lp["ln1"], eps)
        z = dot("bsd,di->bsi", h, m["wz"])
        xc = _conv(dot("bsd,di->bsi", h, m["wx"]), m["conv_wx"], m["conv_bx"])
        bb = _conv(dot("bsd,dn->bsn", h, m["wb"]), m["conv_wb"], m["conv_bb"])
        cc = _conv(dot("bsd,dn->bsn", h, m["wc"]), m["conv_wc"], m["conv_bc"])
        dt = jax.nn.softplus(dot("bsd,dh->bsh", h, m["wdt"]) + m["dt_bias"])
        xh = xc.reshape(*xc.shape[:2], H, P)
        y = _ssd(xh, dt, -jnp.exp(m["a_log"]), bb, cc, dot)
        y = (y + xh * m["d_skip"][:, None]).reshape(*xc.shape[:2], di)
        y = rmsnorm(y * jax.nn.silu(z), m["norm_scale"], eps)
        return x + dot("bsi,id->bsd", y, m["out_proj"]), None

    x, _ = jax.lax.scan(layer, x, p["blocks"]["layer0"])
    x = rmsnorm(x, p["final_norm"], eps)
    if "head" in p["embed"]:
        logits = dot("bsd,dv->bsv", x, p["embed"]["head"])
    else:
        logits = dot("bsd,vd->bsv", x, p["embed"]["tok"])
    return cross_entropy(logits, labels)


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Operations a training step needs per token: forward and backward
    (3x the forward), no recomputation.  Projections count 2 per
    multiply-add.  SSD counts the chunked algorithm the model is defined
    with (chunk Q): within a chunk, C.B and the output over the (Q + 1) / 2
    earlier positions a token sees; across chunks, the state update and
    the state read-out."""
    L = model["n_layers"]
    d, di, H, N = _dims(model)
    P, V = model["ssm_head_dim"], model["vocab_size"]
    q = min(model["ssm_chunk"], seq_len)
    proj = d * (2 * di + 2 * N + H) + di * d
    ssd = 2 * N * (q + 1) / 2 + 2 * H * P * (q + 1) / 2 + 2 * 2 * H * P * N
    fwd = L * (2 * proj + ssd) + 2 * d * V
    return 3.0 * fwd
