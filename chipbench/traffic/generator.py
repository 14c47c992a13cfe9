"""The one generator of inputs.  A traffic mix is a data file
``traffic/<name>.json``; this module reads it and makes the inputs from the
seed.  The same (mix, seed) always gives the same inputs.

One shape of traffic is known, ``"kind": "train"``: one token batch per
step, ``batch`` sequences of ``seq_len`` tokens, ids uniform over the
configuration's vocabulary.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str, root: Path = HERE) -> dict:
    """The traffic mix ``name``: the file ``<root>/<name>.json``."""
    path = Path(root) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng(seed: int, salt: int) -> np.random.Generator:
    """A generator for one purpose (``salt``) of one seed; any whole
    number is a seed."""
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed) % 2 ** 63, int(salt)])))


def train_tokens(mix: dict, vocab_size: int, seed: int,
                 step: int = 1) -> np.ndarray:
    """The batch of training step ``step`` (1, 2, ...): (batch, seq_len +
    1) int32 token ids, inputs and next-token labels.  Every step's rows
    differ."""
    if mix.get("tokens", "uniform") != "uniform":
        raise ValueError(f"unknown token distribution {mix['tokens']!r}")
    return rng(seed, 1000 + step).integers(
        0, vocab_size, (mix["batch"], mix["seq_len"] + 1), dtype=np.int32)
