"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the kernel bodies in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stub

given, settings, st = hypothesis_or_stub()

from repro.kernels import ref
from repro.kernels.offload_quant import dequantize_blocked, quantize_blocked
from repro.kernels.ops import flash_attention, ssd_intra_chunk

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("b,sq,skv,h,kvh,d,causal,dtype", [
    (2, 128, 128, 4, 2, 64, True, jnp.float32),
    (1, 200, 200, 8, 1, 32, True, jnp.float32),      # MQA, ragged seq
    (2, 64, 256, 4, 4, 128, False, jnp.float32),     # cross-shaped
    (1, 384, 384, 6, 2, 112, True, jnp.float32),     # kimi head_dim
    (2, 256, 256, 4, 2, 64, True, jnp.bfloat16),
    (1, 96, 96, 2, 2, 256, True, jnp.float32),       # gemma head_dim
])
def test_flash_attention_sweep(b, sq, skv, h, kvh, d, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, skv, kvh, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, skv, kvh, d), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_sliding_window():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out = flash_attention(q, k, v, causal=True, sliding_window=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, sliding_window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,nc,q,h,p,n", [
    (2, 3, 64, 4, 16, 32),
    (1, 2, 128, 2, 64, 128),   # mamba2-780m tile
    (1, 5, 32, 8, 64, 16),     # jamba tile
])
def test_ssd_intra_chunk_sweep(b, nc, q, h, p, n):
    ks = jax.random.split(KEY, 5)
    xc = jax.random.normal(ks[0], (b, nc, q, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, nc, q, h)))
    da = -jax.nn.softplus(jax.random.normal(ks[2], (b, nc, q, h)))
    bc = jax.random.normal(ks[3], (b, nc, q, n))
    cc = jax.random.normal(ks[4], (b, nc, q, n))
    y, stt = ssd_intra_chunk(xc, dt, da, bc, cc)
    y_ref, st_ref = ref.ssd_intra_chunk_ref(xc, dt, da, bc, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(stt), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(rows=st.integers(1, 40), cols=st.integers(1, 700),
       scale=st.floats(1e-3, 1e3), seed=st.integers(0, 999))
def test_quant_roundtrip_property(rows, cols, scale, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, cols)) * scale
    q, s, meta = quantize_blocked(x)
    xr = dequantize_blocked(q, s, meta)
    assert xr.shape == x.shape
    # per-block error bound: absmax/127 per element
    err = np.abs(np.asarray(xr) - np.asarray(x))
    bound = np.max(np.abs(np.asarray(x))) / 127.0 + 1e-7
    assert err.max() <= bound * 1.01


def test_quant_matches_numpy_ref():
    x = jax.random.normal(KEY, (37, 129)) * 3
    q, s, meta = quantize_blocked(x)
    q2, s2, meta2 = ref.quantize_blocked_ref(np.asarray(x))
    np.testing.assert_array_equal(np.asarray(q).reshape(-1),
                                  q2.reshape(-1))
    xr = ref.dequantize_blocked_ref(np.asarray(q), np.asarray(s), meta2)
    np.testing.assert_allclose(
        xr, np.asarray(dequantize_blocked(q, s, meta)), rtol=1e-6)


def test_flash_inside_model_forward():
    """Kernel path wired through the attention block (prefill/serving)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.attention import attention_block
    from repro.models.layers import ParamBuilder
    from repro.models.attention import init_attention

    cfg = get_config("tinyllama-1.1b").reduced()
    b = ParamBuilder(KEY, jnp.float32)
    init_attention(b, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.head_dim, cfg.qkv_bias)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 96, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(96), (2, 96))
    y0 = attention_block(b.params, x, pos, cfg=cfg)
    cfg2 = dataclasses.replace(cfg, use_flash_kernel=True)
    y1 = attention_block(b.params, x, pos, cfg=cfg2)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("shape,dtype", [
    ((600, 512), jnp.float32),       # several row tiles, a ragged last one
    ((256, 1024), jnp.bfloat16),     # exactly two row tiles
    ((3, 5), jnp.float32),           # fewer rows than one sublane tile
])
def test_quant_row_tiles_match_numpy_ref(shape, dtype):
    x = (jax.random.normal(KEY, shape) * 5).astype(dtype)
    q, s, meta = quantize_blocked(x)
    q2, s2, meta2 = ref.quantize_blocked_ref(np.asarray(x))
    assert q.shape == q2.shape and s.shape == s2.shape
    # bf16 source: a value on a rounding tie may land one step apart
    step = np.abs(np.asarray(q, np.int32) - q2.astype(np.int32)).max()
    assert step <= (0 if dtype == jnp.float32 else 1)
    np.testing.assert_allclose(np.asarray(s), s2, rtol=1e-6)
    xr = dequantize_blocked(q, s, meta)
    assert xr.shape == x.shape and xr.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(xr, np.float32),
        ref.dequantize_blocked_ref(np.asarray(q), np.asarray(s), meta2)
        .astype(np.float32), rtol=1e-2, atol=1e-2)


def test_interpret_switch_follows_the_platform():
    """One switch decides interpret mode: compiled on a TPU, interpreted
    elsewhere; an explicit flag (the compile-only tests) wins."""
    from repro.kernels.backend import interpret_mode, platform_of

    x = jnp.ones((8, 128))
    assert platform_of(x) == jax.default_backend()
    assert interpret_mode(x) is (platform_of(x) != "tpu")
    assert interpret_mode(np.ones(3)) is (jax.default_backend() != "tpu")
    assert interpret_mode(x, False) is False
    assert interpret_mode(x, True) is True
