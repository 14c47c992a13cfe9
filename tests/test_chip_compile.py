"""Compile-only checks for one TPU v5e chip, at the widths the main path
really uses.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``).  Nothing runs:
these tests catch what interpret mode cannot — block shapes off the (8, 128)
tiling, blocks too big for fast memory, programs that do not fit the
device — before any chip time is spent.  The topology is described inside
a fixture, never at import time, so that only the test worker that runs
this file loads the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.kv_block_copy import kv_block_gather, kv_block_scatter
from repro.kernels.offload_quant import dequantize_blocked, quantize_blocked
from repro.launch.steps import build_serve_step
from repro.models.registry import get_model

SERVE_BATCH, SERVE_MAX_LEN = 8, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tinyllama():
    return get_model(get_config("tinyllama-1.1b"))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _cache_leaf_shape(api):
    cache = jax.eval_shape(
        lambda: api.init_cache(SERVE_BATCH, SERVE_MAX_LEN)[0])
    leaf = jax.tree_util.tree_leaves(cache)[0]
    return leaf.shape, leaf.dtype


def test_kv_block_gather_compiles_at_cache_leaf(one_chip, tinyllama):
    shape, dtype = _cache_leaf_shape(tinyllama)
    assert shape == (22, SERVE_BATCH, SERVE_MAX_LEN, 4, 64)
    pool = _spec(shape, dtype, one_chip)
    idx = _spec((4,), np.int32, one_chip)
    compiled = _compile(
        lambda p, i: kv_block_gather(p, i, axis=1, interpret=False),
        pool, idx)
    assert "tpu_custom_call" in compiled.as_text()


def test_kv_block_scatter_compiles_at_cache_leaf(one_chip, tinyllama):
    shape, dtype = _cache_leaf_shape(tinyllama)
    pool = _spec(shape, dtype, one_chip)
    idx = _spec((4,), np.int32, one_chip)
    blocks = _spec(shape[:1] + (4,) + shape[2:], dtype, one_chip)
    compiled = _compile(
        lambda p, i, b: kv_block_scatter(p, i, b, axis=1, interpret=False),
        pool, idx, blocks)
    assert "tpu_custom_call" in compiled.as_text()


def test_offload_quant_compiles_on_full_width_leaf(one_chip):
    x = _spec((2048, 5632), jnp.bfloat16, one_chip)
    compiled = _compile(lambda v: quantize_blocked(v, interpret=False)[:2],
                        x)
    assert "tpu_custom_call" in compiled.as_text()
    rows = 2048 * 5632 // 512
    q = _spec((rows, 512), jnp.int8, one_chip)
    s = _spec((rows, 1), jnp.float32, one_chip)
    meta = ((2048, 5632), "bfloat16", 0)
    compiled = _compile(
        lambda a, b: dequantize_blocked(a, b, meta, interpret=False), q, s)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    q = _spec((1, 2048, 32, 64), jnp.bfloat16, one_chip)
    kv = _spec((1, 2048, 4, 64), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda a, b, c: flash_attention_fwd(a, b, c, interpret=False),
        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_tinyllama_decode_step_compiles_and_fits(one_chip, tinyllama):
    step = build_serve_step(tinyllama, None)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    params = on_chip(tinyllama.abstract_params()[0])
    cache = on_chip(jax.eval_shape(
        lambda: tinyllama.init_cache(SERVE_BATCH, SERVE_MAX_LEN)[0]))
    batch = {"tokens": _spec((SERVE_BATCH, 1), np.int32, one_chip)}
    index = _spec((), np.int32, one_chip)
    compiled = _compile(step, params, cache, batch, index)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 2**30
