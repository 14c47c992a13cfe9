"""The plain references compute what the program's model computes: at a
tiny size in float32 on the CPU, the reference's loss and gradient agree
with the program's jitted loss on the same weights, and the float8 control
does not."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.kinds.train import make_params, register_config
from chipbench.reference import common, dense_lm, mamba2_lm
from chipbench.tests.tiny import DENSE, MAMBA
from chipbench.traffic import generator

CASES = {"dense": (DENSE, dense_lm), "mamba2": (MAMBA, mamba2_lm)}


def _grad_gap(a, b):
    return max(float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_agrees_with_the_program_in_float32(case):
    from repro.models.registry import get_model
    model, ref = CASES[case]
    model = dict(model, name=f"{model['name']}-f32", dtype="float32")
    api = get_model(register_config(model))
    params = make_params(ref, model, 5)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    tok = generator.train_tokens({"batch": 2, "seq_len": 48},
                                 model["vocab_size"], 9)
    data = {"tokens": jnp.asarray(tok[:, :-1]),
            "labels": jnp.asarray(tok[:, 1:])}
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(api.loss)(params, data)
    dot = common.make_dot("float32")
    got, gg = jax.value_and_grad(ref.loss)(
        params, data["tokens"], data["labels"], model, dot)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert _grad_gap(gg, gw) < 1e-3
    low, gl = jax.value_and_grad(ref.loss)(
        params, data["tokens"], data["labels"], model,
        common.make_dot("fp8"))
    assert abs(float(low) - float(want)) > 10 * abs(float(got) - float(want))
    assert _grad_gap(gl, gw) > 10 * _grad_gap(gg, gw)


def test_fp8_rounding_keeps_three_mantissa_bits():
    x = jnp.asarray(np.linspace(-3, 3, 1001), jnp.float32)
    q = common._fp8(x)
    rel = np.abs(np.asarray(q - x)) / np.maximum(np.abs(np.asarray(x)),
                                                 1e-2)
    assert 0 < rel.max() <= 2 ** -4 + 1e-6
