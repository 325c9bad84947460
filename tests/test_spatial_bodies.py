"""The spatial bodies of ``runtime.executor._apply_spatial`` against loops
written from their definitions in NumPy: the k = stride max pool, the
k = stride up-conv (``_tap_matrix`` then ``_depth_to_space``) and the 1x1
conv, on odd extents, where a window or a tap block meets the image's
edge, and on a 23x30 map, the published UNet's fifth level at 368x480.
"""
from __future__ import annotations

import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.builders import _spatial_exec
from repro.core.graph import Graph, Vertex
from repro.runtime.executor import _apply_spatial

#: (input extent, k = stride, cin, cout): cin == cout in two cases, where
#: a weight read as HWOI would still have the right shape
CASES = [((5, 7), 2, 3, 5), ((3, 9), 3, 4, 4), ((7, 4), 2, 6, 2),
         ((8, 6), 2, 8, 8), ((23, 30), 2, 16, 8)]
IDS = ["5x7", "3x9k3", "7x4", "8x6", "23x30"]

#: no plan: every weight whole on-chip; the bodies are the same in
#: either kernel mode
AN = types.SimpleNamespace(frac={}, use_pallas=False, interpret=True)


def _vertex(kind, hw, hw_out, k, cin, cout):
    g = Graph("t")
    g.add(Vertex(f"{kind}_1", kind))
    _spatial_exec(g, f"{kind}_1", cin, cout, hw, hw_out, k=k, stride=k)
    v = g.vertex(f"{kind}_1")
    return v, v.meta["exec"]


def _data(hw, c, seed):
    return np.random.default_rng(seed).standard_normal(
        tuple(hw) + (c,)).astype(np.float32)


def _run(v, spec, x, w=None):
    """One vertex on a ``(H, W, c)`` image, through the executor's
    ``(H*W, c)`` stripes; the result as an image of ``hw_out``."""
    params = {} if w is None else {v.name: jnp.asarray(w)}
    y = _apply_spatial(v, spec, [jnp.asarray(x.reshape(-1, x.shape[-1]))],
                       params, None, AN)
    return np.asarray(y).reshape(tuple(spec["hw_out"]) + (-1,))


@pytest.mark.parametrize("hw,k,c,_", CASES, ids=IDS)
def test_max_pool_matches_numpy_loops(hw, k, c, _):
    """Windows of k x k at stride k; rows and columns past the last whole
    window drop out."""
    ho, wo = hw[0] // k, hw[1] // k
    v, spec = _vertex("pool", hw, (ho, wo), k, c, c)
    x = _data(hw, c, 1)
    want = np.empty((ho, wo, c), np.float32)
    for i in range(ho):
        for j in range(wo):
            want[i, j] = x[i * k:(i + 1) * k, j * k:(j + 1) * k].max(
                axis=(0, 1))
    np.testing.assert_array_equal(_run(v, spec, x), want)


@pytest.mark.parametrize("hw,k,cin,cout", CASES, ids=IDS)
def test_deconv_matches_numpy_loops(hw, k, cin, cout):
    """Input position (i, j) times tap (a, b) of the HWIO weight is output
    position (i*k + a, j*k + b)."""
    h, w_ = hw
    v, spec = _vertex("deconv", hw, (h * k, w_ * k), k, cin, cout)
    x = _data(hw, cin, 2)
    w = _data((k, k, cin), cout, 3)
    want = np.zeros((h * k, w_ * k, cout), np.float64)
    for i in range(h):
        for j in range(w_):
            for a in range(k):
                for b in range(k):
                    want[i * k + a, j * k + b] = (
                        x[i, j].astype(np.float64) @ w[a, b])
    np.testing.assert_allclose(_run(v, spec, x, w), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hw,_,cin,cout", CASES, ids=IDS)
def test_1x1_conv_matches_numpy_loops(hw, _, cin, cout):
    """Every position's channels times the one tap's ``(cin, cout)``."""
    v, spec = _vertex("conv", hw, hw, 1, cin, cout)
    x = _data(hw, cin, 4)
    w = _data((1, 1, cin), cout, 5)
    want = np.zeros(tuple(hw) + (cout,), np.float64)
    for i in range(hw[0]):
        for j in range(hw[1]):
            want[i, j] = x[i, j].astype(np.float64) @ w[0, 0]
    np.testing.assert_allclose(_run(v, spec, x, w), want,
                               rtol=1e-5, atol=1e-5)
