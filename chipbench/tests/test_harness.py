"""The harness end to end on the CPU, at a tiny size, with the look for a
chip skipped: a sound run comes out correct, and a run with the timed path
broken underneath comes out not correct, once per fault a training cell
on one chip can have.  Without an accelerator, or without the program
beside it, the command exits non-zero and prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run as harness
from chipbench.tests.tiny import make_root

CELL = "train.tiny-dense"
SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(root, **kw):
    return harness.run(CELL, SEED, 0.5, False, root=root,
                       require_accelerator=False, **kw)


def test_sound_run_is_correct(root):
    out = _run(root)
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "device_peak_gib",
                                   "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["compiles_in_window"] == 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def _unchanged_state(params, grads, state, **kw):
    return params, state


def _half_batch_loss(logits, labels, z_loss=0.0):
    from repro.models.layers import softmax_cross_entropy as ce
    half = logits.shape[0] // 2
    return ce(logits[:half], labels[:half], z_loss)


FAULTS = {
    "state_unchanged": ("repro.optim.adam", "adamw_update",
                        _unchanged_state),
    "half_batch": ("repro.models.transformer", "softmax_cross_entropy",
                   _half_batch_loss),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    import importlib
    mod, attr, fn = FAULTS[fault]
    monkeypatch.setattr(importlib.import_module(mod), attr, fn)
    out = _run(root)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_no_accelerator_no_result(capsys):
    rc = harness.main(["--workload", "train.minitron-4b-s8.tensile-2x2k-sync",
                       "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_alone_exits_without_result(tmp_path):
    repo = harness.ROOT
    shutil.copy(repo / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(repo / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "train.minitron-4b-s8.tensile-2x2k-sync", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_fp8_control_is_not_correct(root):
    """The control, the reference in the program's place one precision
    below bfloat16, fails the cell's limits on every seed."""
    from chipbench import control
    cell = harness.load_cell(CELL, root)
    rows = list(control.readings(cell, [SEED, 5], ["fp8"]))
    assert len(rows) == 2
    for row in rows:
        assert row["correct"] is False
        assert any(c["value"] > c["limit"] for c in row["checks"].values())
