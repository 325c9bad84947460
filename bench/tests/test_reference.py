"""The reference's arithmetic against the formats it states."""
from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402


def _blocks() -> jax.Array:
    """Rows of 96 channels: random scales, an all-zero block, exact powers
    of two, halves that round to even, and a block near the clip."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((64, 96)) * np.exp2(rng.integers(-20, 20, (64, 1)))
    x[0, :32] = 0.0
    x[1, :32] = 4.0
    x[1, 32:64] = -0.25
    x[2, :32] = np.linspace(-1, 1, 32)
    x[3, :32] = 1.5 * np.exp2(-6) * np.arange(32)
    return jnp.asarray(x, jnp.float32)


def test_bfp8_is_the_programs_codec():
    """Written from the format; the program's codec keeps the same
    exponents, and the same mantissas but where ``x / step`` lies on a tie
    (it divides by a step that its ``exp2`` rounds)."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.ref import bfp8_quant_ref
    x = _blocks()
    man, exp = bfp8_quant_ref(x, 32)
    my_man, my_exp = reference.bfp8_parts(x, 32)
    np.testing.assert_array_equal(np.asarray(my_exp),
                                  np.asarray(exp, np.float32))
    q = np.asarray(x).reshape(64, 3, 32) / np.exp2(
        np.asarray(my_exp)[..., None] - 6)
    tie = np.abs(np.abs(q - np.trunc(q)) - 0.5) < 1e-4
    diff = np.abs(np.asarray(my_man) - np.asarray(man, np.float32))
    assert diff.max() <= 1
    assert not np.any(diff.reshape(64, 3, 32)[~tie])
    assert tie.sum() > 0 and diff.sum() <= tie.sum()


def test_bfp8_keeps_eight_bits_per_block():
    x = _blocks()
    y = reference.bfp8(x, 32)
    amax = jnp.max(jnp.abs(x.reshape(64, 3, 32)), axis=-1)
    err = jnp.max(jnp.abs((y - x).reshape(64, 3, 32)), axis=-1)
    # a step of 2**(ceil(log2 amax) - 6) rounds to within half a step
    assert bool(jnp.all(err <= amax / 64 + 1e-30))
    assert float(jnp.max(jnp.abs(y[0, :32]))) == 0.0
    np.testing.assert_array_equal(np.asarray(y[1, :64]),
                                  np.asarray(x[1, :64]))


def _net():
    return [
        {"name": "input_1", "kind": "input", "inputs": [], "cin": 32,
         "cout": 32, "m": 8, "m_out": 8},
        {"name": "conv_2", "kind": "conv", "inputs": ["input_1"], "cin": 32,
         "cout": 32, "m": 8, "m_out": 8},
        {"name": "concat_3", "kind": "concat",
         "inputs": ["input_1", "conv_2"], "cin": 64, "cout": 64, "m": 8,
         "m_out": 8},
        {"name": "output_4", "kind": "output", "inputs": ["concat_3"],
         "cin": 64, "cout": 64, "m": 8, "m_out": 8},
    ]


ARITH = {"matmul_inputs": "float32", "storage": "float32", "bfp8_block": 32,
         "bfp8_edges": []}


def test_forward_states_its_arithmetic():
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (8, 32))
    w = {"conv_2": jax.random.normal(kw, (32, 32))}
    hi = jax.lax.Precision.HIGHEST
    y = reference.forward(_net(), w, x, ARITH)
    np.testing.assert_allclose(
        np.asarray(y), np.concatenate([x, jnp.dot(x, w["conv_2"],
                                                  precision=hi)], 1).ravel(),
        rtol=1e-6, atol=1e-5)
    bf = jnp.bfloat16
    y = reference.forward(_net(), w, x, dict(ARITH, matmul_inputs="bfloat16"))
    one_pass = jnp.dot(x.astype(bf).astype(jnp.float32),
                       w["conv_2"].astype(bf).astype(jnp.float32),
                       precision=hi)
    np.testing.assert_allclose(np.asarray(y)[:, None].reshape(8, 64)[:, 32:],
                               np.asarray(one_pass), rtol=1e-6, atol=1e-5)
    y = reference.forward(_net(), w, x,
                          dict(ARITH, bfp8_edges=[["input_1", "concat_3"]]))
    np.testing.assert_array_equal(np.asarray(y).reshape(8, 64)[:, :32],
                                  np.asarray(reference.bfp8(x, 32)))
    y = reference.forward(_net(), w, x,
                          reference.control_arithmetic(ARITH))
    assert y.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(y.astype(bf).astype(jnp.float32)))


def test_an_edge_not_in_the_model_is_refused():
    w = {"conv_2": jnp.zeros((32, 32))}
    with pytest.raises(ValueError, match="not in the model"):
        reference.forward(_net(), w, jnp.zeros((8, 32)),
                          dict(ARITH, bfp8_edges=[["act_3", "concat_33"]]))
