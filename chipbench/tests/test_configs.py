"""Each configuration file loads, registers with repro.configs at run time
and has the parameter count its cut states; BENCHMARK.json keeps to the
shape the harness reads."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from chipbench.kinds.train import register_config

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

# ModelConfig.param_count() of each cut (embedding rows not padded)
PARAMS = {"minitron-4b-s8": 523_788_288, "mamba2-780m": 779_909_376}


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_config_registers_with_its_parameter_count(name):
    from repro.configs import CONFIGS, get_config
    from repro.models.registry import get_model
    doc = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert doc["name"] == doc["model"]["name"] == name
    for key in ("source", "reduced", "assumed", "deployment"):
        assert key in doc, key
    cfg = register_config(doc["model"])
    assert CONFIGS[name] is cfg
    assert get_config(name).param_count() == PARAMS[name]
    assert get_model(get_config(name)).cfg.name == name
    for key, change in doc["reduced"].items():
        assert doc["source_config"][key] == change["source"] != change["here"]


def test_prefix_layers_register():
    """A leading dense layer (``prefix``) before a block of MoE layers
    registers as LayerSpecs, and the count matches the program's tree,
    which adds the final norm."""
    import math

    import jax

    from repro.configs.base import LayerSpec
    from repro.models.registry import get_model
    model = {"name": "tiny-prefix-moe", "arch_family": "moe", "n_layers": 3,
             "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
             "d_ff": 32, "vocab_size": 512, "n_experts": 4, "top_k": 2,
             "n_shared_experts": 1, "prefix_d_ff": 96,
             "prefix": [{"mixer": "attn", "ffn": "dense"}],
             "block": [{"mixer": "attn", "ffn": "moe"}]}
    cfg = register_config(model)
    assert cfg.prefix == (LayerSpec("attn", "dense"),)
    assert cfg.block == (LayerSpec("attn", "moe"),)
    # embeddings 65,536; the dense layer 12,288 + 18,432 + 128; two MoE
    # layers of 12,288 + 5 x 6,144 + 256 + 128
    assert cfg.param_count() == 183_168
    tree, _ = get_model(cfg).abstract_params()
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(tree)) \
        == cfg.param_count() + model["d_model"]


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        doc = json.loads((ROOT / c["file"]).read_text())
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
    used = set()
    cells = [w["name"] for w in bench["workloads"]]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        used.add(w["config"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
        assert len(w["why"]) <= 200
    assert used == set(configs)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
