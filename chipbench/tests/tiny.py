"""A benchmark of tiny configurations for tests on the CPU: the same
harness, drivers, references and readers, with small shapes."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

DENSE = {"name": "tiny-dense", "arch_family": "dense", "n_layers": 2,
         "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
         "d_ff": 128, "vocab_size": 512, "mlp_act": "relu2",
         "rope_theta": 10000.0, "norm_eps": 1e-05,
         "tie_embeddings": False, "dtype": "bfloat16"}
MAMBA = {"name": "tiny-mamba", "arch_family": "ssm", "n_layers": 2,
         "d_model": 64, "n_heads": 0, "n_kv_heads": 0, "head_dim": 0,
         "d_ff": 0, "vocab_size": 300,
         "block": [{"mixer": "mamba", "ffn": "none"}], "ssm_state": 16,
         "ssm_head_dim": 16, "ssm_expand": 2, "ssm_chunk": 16,
         "ssm_conv_width": 4, "norm_eps": 1e-05, "tie_embeddings": True,
         "dtype": "bfloat16"}
TRAFFIC = json.loads((BENCH / "traffic" / "train_2x2k_tensile_sync.json")
                     .read_text())
TRAFFIC.update(batch=2, seq_len=32, why="tiny")
# Set as a cell's limits are, from readings on the CPU at this size: the
# sound program over six seeds reads at most 0.00039, 0.00166 and 0.00117,
# the float8 control over four at least 0.00142, 0.0117 and 0.0023.
LIMITS = {"loss_gap": 0.001, "grad_gap": 0.005, "change_gap": 0.003}


def make_root(tmp: Path, limits=None) -> Path:
    """A checkout-like tree: BENCHMARK.json naming two tiny training cells,
    their configuration, traffic and limit files, and the real metric
    entries, readers and peaks, as they stand."""
    root = Path(tmp)
    bench = root / "chipbench"
    for sub in ("configs", "traffic", "cells"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    (bench / "traffic" / "tiny_train.json").write_text(json.dumps(TRAFFIC))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    configs, workloads = [], []
    for model, ref in ((DENSE, "dense_lm"), (MAMBA, "mamba2_lm")):
        name = model["name"]
        (bench / "configs" / f"{name}.json").write_text(json.dumps(
            {"name": name, "reference": ref, "model": model}))
        configs.append({"name": name, "source": "test", "reduced": [],
                        "file": f"chipbench/configs/{name}.json",
                        "why": "test"})
        cell = f"train.{name}"
        workloads.append({"name": cell, "config": name,
                          "traffic": "tiny_train", "chips": 1,
                          "why": "test"})
        (bench / "cells" / f"{cell}.json").write_text(
            json.dumps({"limits": limits or LIMITS}))
    real.update(configs=configs, workloads=workloads)
    (root / "BENCHMARK.json").write_text(json.dumps(real))
    return root
