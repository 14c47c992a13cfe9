"""Launch-layer units: HLO collective parsing, roofline math, sharding
rules, and the §Perf levers (fused CE, microbatching, a2a MoE wiring)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, shapes_for, skipped_shapes_for
from repro.configs.base import ALL_SHAPES, ShapeSpec
from repro.launch.dryrun import (model_flops_for, parse_collectives)


# ----------------------------------------------------------- HLO parsing
SAMPLE_HLO = """
  %ag = bf16[8,128,256] all-gather(bf16[8,8,256] %x), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={1}
  %ar = f32[1024] all-reduce(f32[1024] %y), replica_groups={{0,1,2,3}}, to_apply=%sum
  %cp = bf16[64,64] collective-permute(bf16[64,64] %z), source_target_pairs={{0,1}}
  %a2a.1 = f32[16,32] all-to-all(f32[16,32] %w), replica_groups={{0,1,2,3,4,5,6,7}}
"""


def test_parse_collectives_kinds_and_sizes():
    c = parse_collectives(SAMPLE_HLO)
    assert set(c) == {"all-gather", "all-reduce", "collective-permute",
                      "all-to-all"}
    assert c["all-gather"]["bytes"] == 8 * 128 * 256 * 2
    # ring all-reduce: 2·size·(n-1)/n with n=4
    assert c["all-reduce"]["wire_bytes"] == pytest.approx(
        2 * 1024 * 4 * 3 / 4)
    assert c["collective-permute"]["wire_bytes"] == 64 * 64 * 2


def test_parse_collectives_ignores_done_ops():
    txt = "%d = f32[8] all-reduce-done(f32[8] %s)"
    assert parse_collectives(txt) == {}


# --------------------------------------------------------- model flops
def test_model_flops_train_vs_decode():
    cfg = get_config("tinyllama-1.1b")
    train = [s for s in ALL_SHAPES if s.name == "train_4k"][0]
    decode = [s for s in ALL_SHAPES if s.name == "decode_32k"][0]
    f_train = model_flops_for(cfg, train)
    f_dec = model_flops_for(cfg, decode)
    n = cfg.active_param_count()
    assert f_train == pytest.approx(6 * n * 256 * 4096)
    assert f_dec == pytest.approx(2 * n * 128)


def test_moe_model_flops_use_active_params():
    kimi = get_config("kimi-k2-1t-a32b")
    train = [s for s in ALL_SHAPES if s.name == "train_4k"][0]
    f = model_flops_for(kimi, train)
    assert f < 6 * kimi.param_count() * 256 * 4096 * 0.1  # 32B << 1T


# ------------------------------------------------------------ shape sets
def test_shape_assignment_and_skips():
    for arch in ("gemma-2b", "qwen2.5-14b", "whisper-base"):
        cfg = get_config(arch)
        names = [s.name for s in shapes_for(cfg)]
        assert names == ["train_4k", "prefill_32k", "decode_32k"]
        assert skipped_shapes_for(cfg)[0][0].name == "long_500k"
    for arch in ("jamba-1.5-large-398b", "mamba2-780m"):
        cfg = get_config(arch)
        assert "long_500k" in [s.name for s in shapes_for(cfg)]
        assert not skipped_shapes_for(cfg)


# --------------------------------------------------------- sharding rules
def test_mesh_rules_head_divisibility_fallback():
    from tests.test_distribution import run_with_devices
    out = run_with_devices("""
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.launch.sharding import MeshRules
        mesh = make_mesh((1, 16), ("data", "model"))
        # qwen: 40 heads % 16 != 0 -> replicated heads
        r1 = MeshRules(mesh, cfg=get_config("qwen2.5-14b"))
        assert r1.table["heads"] is None
        # kimi: 64 heads ok; kv 8 not
        r2 = MeshRules(mesh, cfg=get_config("kimi-k2-1t-a32b"))
        assert r2.table["heads"] == "model"
        assert r2.table["kv_heads"] is None
        print("RULES_OK")
    """, n=16)
    assert "RULES_OK" in out


# ----------------------------------------------------------- perf levers
def test_fused_ce_matches_plain():
    import dataclasses
    from repro.models.registry import get_model
    cfg = get_config("tinyllama-1.1b").reduced()
    api = get_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    batch = api.input_specs(ShapeSpec("s", 64, 2, "train"), abstract=False)
    l0 = float(api.loss(params, batch))
    api2 = get_model(dataclasses.replace(cfg, loss_chunk=16))
    l1 = float(api2.loss(params, batch))
    assert abs(l0 - l1) < 1e-4 * max(abs(l0), 1)


def test_microbatched_step_matches_full_batch():
    from repro.launch.sharding import MeshRules
    from repro.launch.steps import TrainStepConfig, build_train_step, \
        opt_state_for
    from repro.models.registry import get_model
    cfg = get_config("tinyllama-1.1b").reduced()
    api = get_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    opt = opt_state_for(params)
    batch = api.input_specs(ShapeSpec("s", 32, 4, "train"), abstract=False)
    s1 = build_train_step(api, None, TrainStepConfig(microbatches=1))
    s2 = build_train_step(api, None, TrainStepConfig(microbatches=2))
    p1, _, m1 = jax.jit(s1)(params, opt, batch)
    p2, _, m2 = jax.jit(s2)(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-4)


def test_a2a_moe_single_device_fallback():
    """Without a model axis the a2a implementation must fall back to the
    scatter path and stay numerically correct."""
    import dataclasses
    from repro.models.registry import get_model
    cfg = get_config("moonshot-v1-16b-a3b").reduced()
    cfg.moe_impl = "a2a"
    cfg.capacity_factor = 8.0
    api = get_model(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    batch = api.input_specs(ShapeSpec("s", 32, 2, "train"), abstract=False)
    loss = api.loss(params, batch)
    assert np.isfinite(float(loss))


# ----------------------------------------------------------- compile cache
@pytest.mark.parametrize("placed", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir_is_fixed_or_placed(monkeypatch, placed):
    """The persistent compile cache is where JAX_COMPILATION_CACHE_DIR
    says (left to JAX), else at one fixed directory in the checkout."""
    from repro.launch import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv(cc.ENV, placed)
    else:
        monkeypatch.delenv(cc.ENV, raising=False)
    try:
        used = cc.setup_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if placed:
        assert used == placed and after == before
    else:
        assert used == after == str(cc.DEFAULT_DIR)
        assert cc.DEFAULT_DIR.name == ".jax_cache"
        assert (cc.DEFAULT_DIR.parent / "pyproject.toml").is_file()
