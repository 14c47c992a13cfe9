"""The generator: the same seed gives the same inputs, every step's
batch differs, and a mix is found by name from its data file alone."""
from __future__ import annotations

import json

import numpy as np

from chipbench.traffic import generator

def test_train_tokens_are_a_function_of_the_seed():
    mix = generator.load("train_2x2k_tensile_sync")
    big = 2 ** 31 + 12345
    a = generator.train_tokens(mix, 32000, big)
    assert a.shape == (2, 2049) and a.dtype == np.int32
    assert np.array_equal(a, generator.train_tokens(mix, 32000, big))
    assert not np.array_equal(a, generator.train_tokens(mix, 32000, 7))
    assert not np.array_equal(a, generator.train_tokens(mix, 32000, big, 2))
    assert a.min() >= 0 and a.max() < 32000


def test_a_mix_is_added_by_a_data_file(tmp_path):
    (tmp_path / "dummy_mix.json").write_text(json.dumps(
        {"kind": "train", "batch": 3, "seq_len": 16}))
    mix = generator.load("dummy_mix", tmp_path)
    assert generator.train_tokens(mix, 100, 1).shape == (3, 17)
