"""The reference's spatial layers against loops written from their
definitions in NumPy, at tiny sizes, on inputs and weights rounded to
bfloat16 (so every product is exact in float32 and only the order of the
sums differs); a small 2-D UNet against one written out by hand; and the
published UNet's work count against the program's cost model.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, run, system, work  # noqa: E402
from bench.models import unet2d  # noqa: E402
from bench.observe import CompileCounter, Spans  # noqa: E402

ARITH = {"matmul_inputs": "bfloat16", "storage": "float32", "bfp8_block": 32,
         "bfp8_edges": []}


def _bf16(rng, shape, scale=1.0) -> np.ndarray:
    """Normal draws rounded to bfloat16, as float64."""
    x = jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    return np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32), np.float64)


def _one(layer: dict, shape, cin: int) -> list[dict]:
    """input -> ``layer`` -> output."""
    L = dict({"inputs": ["input_1"], "cin": cin, "shape": list(shape)},
             **layer)
    return [{"name": "input_1", "kind": "input", "inputs": [], "cin": cin,
             "cout": cin, "shape": list(shape)},
            L,
            {"name": "output_9", "kind": "output", "inputs": [L["name"]],
             "cin": L["cout"], "cout": L["cout"]}]


def _forward(net, weights, x, arith=ARITH) -> np.ndarray:
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    return np.asarray(reference.forward(net, w, jnp.asarray(x, jnp.float32),
                                        arith), np.float64)


def _close(got, want, rtol=2e-6, atol=2e-6):
    np.testing.assert_allclose(got, np.asarray(want).ravel(), rtol=rtol,
                               atol=atol)


def np_conv(x, w, stride, groups=1, bias=None) -> np.ndarray:
    """Zero-padded by ``k // 2``: each output position sums its taps."""
    nd = x.ndim - 1
    k, cin_g, cout = w.shape[:nd], w.shape[nd], w.shape[nd + 1]
    pad = [kk // 2 for kk in k]
    xp = np.pad(x, [(p, p) for p in pad] + [(0, 0)])
    out = [(n + 2 * p - kk) // s + 1
           for n, p, kk, s in zip(x.shape[:nd], pad, k, stride)]
    og = cout // groups
    y = np.zeros(out + [cout])
    for pos in np.ndindex(*out):
        for tap in np.ndindex(*k):
            v = xp[tuple(o * s + t for o, s, t in zip(pos, stride, tap))]
            for g in range(groups):
                ins, outs = slice(g * cin_g, (g + 1) * cin_g), \
                    slice(g * og, (g + 1) * og)
                y[pos][outs] += v[ins] @ w[tap][:, outs]
    return y if bias is None else y + bias


def np_deconv(x, w) -> np.ndarray:
    nd = x.ndim - 1
    k = w.shape[:nd]
    y = np.zeros([n * kk for n, kk in zip(x.shape[:nd], k)] + [w.shape[-1]])
    for pos in np.ndindex(*x.shape[:nd]):
        for tap in np.ndindex(*k):
            y[tuple(p * kk + t for p, kk, t in zip(pos, k, tap))] = \
                x[pos] @ w[tap]
    return y


def np_pool(x, op, k, stride, pad) -> np.ndarray:
    nd = x.ndim - 1
    fill = -np.inf if op == "max" else 0.0
    xp = np.pad(x, [(p, p) for p in pad] + [(0, 0)], constant_values=fill)
    out = [(n + 2 * p - kk) // s + 1
           for n, p, kk, s in zip(x.shape[:nd], pad, k, stride)]
    y = np.zeros(out + [x.shape[-1]])
    for pos in np.ndindex(*out):
        win = xp[tuple(slice(o * s, o * s + kk)
                       for o, s, kk in zip(pos, stride, k))]
        win = win.reshape(-1, x.shape[-1])
        y[pos] = win.max(0) if op == "max" else win.sum(0) / math.prod(k)
    return y


def np_bfp8(x, block) -> np.ndarray:
    """Per position, blocks of ``block`` channels, the last one shorter."""
    y = np.zeros_like(x)
    for pos in np.ndindex(*x.shape[:-1]):
        for lo in range(0, x.shape[-1], block):
            b = x[pos][lo:lo + block]
            amax = np.abs(b).max()
            e = 0 if amax == 0 else int(np.ceil(np.log2(amax)))
            step = 2.0 ** (e - 6)
            y[pos][lo:lo + block] = np.clip(np.round(b / step), -127,
                                            127) * step
    return y


CONVS = [
    # (name, input extent, cin, cout, k, stride, groups, bias)
    ("3x3 s1", (6, 7), 5, 4, 3, 1, 1, False),
    ("3x3 s1 bias", (6, 7), 5, 4, 3, 1, 1, True),
    ("3x3 s2", (7, 8), 5, 6, 3, 2, 1, False),
    ("3x3 s2 bias", (7, 8), 5, 6, 3, 2, 1, True),
    ("dw 3x3x3 s(1,2,2)", (4, 7, 8), 6, 6, 3, (1, 2, 2), 6, False),
    ("dw 3x3x3 s(1,2,2) bias", (4, 7, 8), 6, 6, 3, (1, 2, 2), 6, True),
    ("1x3x3", (3, 6, 5), 4, 5, (1, 3, 3), 1, 1, False),
    ("5x1x1", (7, 3, 4), 4, 3, (5, 1, 1), 1, 1, True),
]


@pytest.mark.parametrize("case", CONVS, ids=[c[0] for c in CONVS])
def test_conv_is_the_loop(case):
    _, shape, cin, cout, k, stride, groups, bias = case
    rng = np.random.default_rng(1)
    nd = len(shape)
    kk = (k,) * nd if isinstance(k, int) else k
    ss = (stride,) * nd if isinstance(stride, int) else stride
    L = {"name": "conv_2", "kind": "conv", "cout": cout, "k": k,
         "stride": stride, "groups": groups, "bias": bias}
    net = _one(L, shape, cin)
    x = _bf16(rng, shape + (cin,))
    w = {"conv_2": _bf16(rng, kk + (cin // groups, cout), 0.3)}
    if bias:
        w["conv_2.bias"] = _bf16(rng, (cout,))
    assert reference.weight_shapes(net) == {n: v.shape for n, v in w.items()}
    want = np_conv(x, w["conv_2"], ss, groups, w.get("conv_2.bias"))
    _close(_forward(net, w, x), want)
    assert tuple(reference.out_shape(net[1])) == want.shape[:-1]


def test_deconv_2x2_is_the_loop():
    rng = np.random.default_rng(2)
    L = {"name": "deconv_2", "kind": "deconv", "cout": 3, "k": 2,
         "shape_out": [8, 10]}
    net = _one(L, (4, 5), 6)
    x = _bf16(rng, (4, 5, 6))
    w = {"deconv_2": _bf16(rng, (2, 2, 6, 3), 0.4)}
    _close(_forward(net, w, x), np_deconv(x, w["deconv_2"]))


POOLS = [
    # (name, extent, op, k, stride, pad)
    ("max 2x2/s2", (6, 8), "max", 2, 2, 0),
    ("max 5x5/s1/pad2", (6, 7), "max", 5, 1, 2),
    ("mean 2x2/s2", (6, 8), "mean", 2, 2, 0),
]


@pytest.mark.parametrize("case", POOLS, ids=[c[0] for c in POOLS])
def test_pool_is_the_loop(case):
    _, shape, op, k, stride, pad = case
    rng = np.random.default_rng(3)
    L = {"name": "pool_2", "kind": "pool", "cout": 5, "op": op, "k": k,
         "stride": stride, "pad": pad}
    net = _one(L, shape, 5)
    x = _bf16(rng, shape + (5,))
    want = np_pool(x, op, (k,) * 2, (stride,) * 2, (pad,) * 2)
    _close(_forward(net, {}, x), want)


def test_global_mean_is_the_mean_over_the_extent():
    rng = np.random.default_rng(4)
    L = {"name": "pool_2", "kind": "pool", "cout": 5, "op": "mean",
         "shape_out": [1, 1, 1]}
    net = _one(L, (3, 4, 5), 5)
    x = _bf16(rng, (3, 4, 5, 5))
    want = x.reshape(-1, 5).sum(0) / 60
    _close(_forward(net, {}, x), want)


def test_nearest_upsample_is_the_loop():
    rng = np.random.default_rng(5)
    L = {"name": "upsample_2", "kind": "upsample", "cout": 3,
         "shape_out": [6, 4, 9]}
    net = _one(L, (3, 2, 3), 3)
    x = _bf16(rng, (3, 2, 3, 3))
    want = np.zeros((6, 4, 9, 3))
    for pos in np.ndindex(6, 4, 9):
        want[pos] = x[pos[0] // 2, pos[1] // 2, pos[2] // 3]
    _close(_forward(net, {}, x), want, rtol=0, atol=0)


@pytest.mark.parametrize("fn,f", [
    ("silu", lambda v: v / (1 + np.exp(-v))),
    ("sigmoid", lambda v: 1 / (1 + np.exp(-v))),
    ("relu", lambda v: np.maximum(v, 0))])
def test_activation_is_its_function(fn, f):
    rng = np.random.default_rng(6)
    L = {"name": "act_2", "kind": "act", "cout": 7, "fn": fn}
    net = _one(L, (4, 5), 7)
    x = _bf16(rng, (4, 5, 7), 3.0)
    _close(_forward(net, {}, x), f(x))


def test_squeeze_excitation_broadcasts_mul_and_add_slices():
    """Global mean, sigmoid, a broadcast ``mul``, a residual ``add`` and a
    channel ``slice``, as an SE block and C2f's split use them."""
    rng = np.random.default_rng(7)
    sp = [2, 4, 5]
    net = [
        {"name": "input_1", "kind": "input", "inputs": [], "cin": 6,
         "cout": 6, "shape": sp},
        {"name": "pool_2", "kind": "pool", "inputs": ["input_1"], "cin": 6,
         "cout": 6, "shape": sp, "op": "mean", "shape_out": [1, 1, 1]},
        {"name": "act_3", "kind": "act", "inputs": ["pool_2"], "cin": 6,
         "cout": 6, "shape": [1, 1, 1], "fn": "sigmoid"},
        {"name": "mul_4", "kind": "mul", "inputs": ["input_1", "act_3"],
         "cin": 6, "cout": 6, "shape": sp},
        {"name": "add_5", "kind": "add", "inputs": ["act_3", "mul_4"],
         "cin": 6, "cout": 6, "shape": sp},
        {"name": "slice_6", "kind": "slice", "inputs": ["add_5"], "cin": 6,
         "cout": 4, "shape": sp, "lo": 1, "hi": 5},
        {"name": "output_7", "kind": "output", "inputs": ["slice_6"],
         "cin": 4, "cout": 4},
    ]
    x = _bf16(rng, tuple(sp) + (6,))
    gate = 1 / (1 + np.exp(-x.reshape(-1, 6).mean(0)))
    want = (gate + x * gate)[..., 1:5]
    _close(_forward(net, {}, x), want)


def test_bfp8_gives_a_short_last_block_its_own_exponent():
    """c = 54: blocks of 32 and 22 channels; the short block's largest
    magnitude sets its step, through ``bfp8`` and along an edge."""
    rng = np.random.default_rng(8)
    x = rng.standard_normal((3, 4, 54)) * np.exp2(rng.integers(-8, 8,
                                                                (3, 4, 1)))
    x[0, 0, :32] *= 100.0                 # the first block far larger
    x[0, 1, 32:] = 0.0                    # an all-zero short block
    x[0, 2, 32:] = 0.25                   # a power of two
    x = np.asarray(jnp.asarray(x, jnp.float32), np.float64)
    want = np_bfp8(x, 32)
    got = np.asarray(reference.bfp8(jnp.asarray(x, jnp.float32), 32))
    np.testing.assert_array_equal(got, want)
    man, e = reference.bfp8_parts(jnp.asarray(x, jnp.float32), 32)
    assert man.shape == (3, 4, 54) and e.shape == (3, 4, 2)
    L = {"name": "act_2", "kind": "act", "cout": 54}
    net = _one(L, (3, 4), 54)
    arith = dict(ARITH, bfp8_edges=[["input_1", "act_2"]])
    _close(_forward(net, {}, x, arith), np.maximum(want, 0), rtol=0, atol=0)


def test_a_shape_that_disagrees_is_refused():
    L = {"name": "conv_2", "kind": "conv", "cout": 4, "k": 3, "stride": 2,
         "shape_out": [6, 7]}
    with pytest.raises(ValueError, match="output extent"):
        reference.forward(_one(L, (6, 7), 4),
                          {"conv_2": jnp.zeros((3, 3, 4, 4))},
                          jnp.zeros((6, 7, 4)), ARITH)


def _hand_unet(x, w, levels):
    """UNet written out: 3x3 'same' convs as nine shifted matmuls, 2x2 max
    pools by reshape, 2x2 up-convs by interleaving four matmuls."""
    ws = iter(w[n] for n in sorted(w, key=lambda n: int(n.split("_")[1])))
    hi = jax.lax.Precision.HIGHEST

    def conv3(v):
        k = next(ws)
        h, wd, _ = v.shape
        p = jnp.pad(v, ((1, 1), (1, 1), (0, 0)))
        return sum(jnp.dot(p[i:i + h, j:j + wd], k[i, j], precision=hi)
                   for i in range(3) for j in range(3))

    def block(v):
        for _ in range(2):
            v = jnp.maximum(conv3(v), 0.0)
        return v

    skips = []
    for lv in range(levels):
        x = block(x)
        if lv < levels - 1:
            skips.append(x)
            h, wd, c = x.shape
            x = x.reshape(h // 2, 2, wd // 2, 2, c).max(axis=(1, 3))
    for _ in range(levels - 1):
        k = next(ws)
        h, wd, _ = x.shape
        up = jnp.stack([jnp.stack([jnp.dot(x, k[i, j], precision=hi)
                                   for j in range(2)], axis=2)
                        for i in range(2)], axis=1)
        x = up.reshape(2 * h, 2 * wd, k.shape[-1])
        x = block(jnp.concatenate([skips.pop(), x], axis=-1))
    return jnp.dot(x, next(ws)[0, 0], precision=hi).ravel()


def test_small_unet_is_the_hand_written_one():
    net = unet2d.layers([32, 48], 3, 8, 3, 4)
    k_w, k_x = jax.random.split(jax.random.PRNGKey(9))
    w = reference.make_weights(net, k_w)
    x = reference.make_frames(net, k_x, (), 3)
    arith = dict(ARITH, matmul_inputs="float32")
    y = reference.forward(net, w, x, arith)
    want = _hand_unet(x, w, 3)
    assert y.shape == want.shape == (32 * 48 * 4,)
    assert float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want)) < 1e-6


def test_published_unet_counts_the_programs_macs():
    """3x368x480, base 64, 5 levels, 32 classes: 130,176,614,400 MAC, conv
    by conv the program's cost model (Table III says 130.12 G), named and
    wired as its graph."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.builders import build_unet
    net = unet2d.layers([368, 480], 3, 64, 5, 32)
    g = build_unet((368, 480), 3, 64, 5, 32)
    assert work.frame_macs(net) == 130_176_614_400
    assert work.frame_macs(net) == g.total_macs()
    assert [(L["name"], L["kind"]) for L in net] == [
        (v.name, v.kind) for v in g.vertices()]
    for L in net:
        assert [e.src for e in g.in_edges(L["name"])] == L["inputs"]
    macs = {c["name"]: c["macs"] for c in work.convs(net)}
    assert macs == {v.name: v.work_macs for v in g.vertices()
                    if v.kind in reference.WEIGHT_KINDS}
    assert reference.weight_shapes(net)["deconv_26"] == (2, 2, 1024, 512)


SMALL_2D = [
    {"name": "input_1", "kind": "input", "inputs": [], "cin": 8, "cout": 8,
     "shape": [6, 10]},
    {"name": "conv_2", "kind": "conv", "inputs": ["input_1"], "cin": 8,
     "cout": 8, "shape": [6, 10], "k": 3},
    {"name": "act_3", "kind": "act", "inputs": ["conv_2"], "cin": 8,
     "cout": 8, "shape": [6, 10]},
    {"name": "output_4", "kind": "output", "inputs": ["act_3"], "cin": 8,
     "cout": 8},
]


def test_callers_take_a_spatial_input_layer():
    """Frames, the stream driver's pool and sample, the control and the
    step's compile all take their frame shape from a 2-D input layer."""
    net = SMALL_2D
    assert reference.input_shape(net) == (6, 10, 8)
    k_w, k_x = jax.random.split(reference.seed_key(2 ** 40 + 3))
    weights = reference.make_weights(net, k_w)
    frames = reference.make_frames(net, k_x, (3, 2), 3)
    assert frames.shape == (3, 2, 6, 10, 8)
    assert not bool(jnp.any(frames[..., 3:]))
    ctl = reference.control_fn(net, ARITH)
    assert ctl(weights, frames[0]).shape == (2, 6 * 10 * 8)

    step = jax.jit(lambda params, xs: jax.vmap(
        lambda x: reference.forward(net, params, x, ARITH))(xs))
    executor = types.SimpleNamespace(fn=step, params=weights, microbatches=2)
    compiled = types.SimpleNamespace(executor=executor,
                                     run=lambda xs: step(weights, xs))
    assert "f32[2,6,10,8]" in system.step_hlo(compiled, net)

    cfg = {"frame_channels": 3, "arithmetic": ARITH,
           "limits": {"frame_rel_l2": 1e-6}}
    traffic = json.loads((ROOT / "bench/traffic/stream.json").read_text())
    ctx = types.SimpleNamespace(
        system=compiled, traffic=traffic, net=net, frame_key=k_x, cfg=cfg,
        seconds=0.3, trace=False, spans=Spans(), compiles=CompileCounter(),
        rng=np.random.default_rng(0), t_start=0.0, log=lambda _: None)
    driver = run.load_module(ROOT / "bench/drivers/stream.py", "stream_2d")
    out = driver.run(ctx)
    assert out["attempted"] > 0
    assert {x.shape for x, _ in out["samples"]} == {(6, 10, 8)}
    assert run.check(cfg, net, weights, out["samples"])[
        "frame_rel_l2"]["value"] == 0.0
    compiled.run = lambda xs: ctl(weights, xs)
    out = driver.run(ctx)
    assert run.check(cfg, net, weights, out["samples"])[
        "frame_rel_l2"]["value"] > 1e-4
