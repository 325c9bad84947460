"""The published 2-D UNet (``core.builders.build_unet``) on the normal path.

A small UNet (32x48 RGB frames, base 32, 3 levels) goes through
``repro.compile`` pipelined with the Pallas kernels (interpret mode here)
and is compared with the benchmark's plain reference
(``bench/reference.py`` with ``bench/models/unet2d.py``) on the
reference's seeded weights, handed to the program by name as the
benchmark does.  Off a TPU a float32 dot is exact, so the reference runs
in float32: program and reference then differ only by the order of each
conv's float32 sum (the kernel sums tap by tap, XLA's conv in its own
order), far under 1e-5 of a frame.
"""
from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import build_unet
from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402

KW = {"input_hw": [32, 48], "cin": 3, "base": 32, "levels": 3,
      "n_classes": 32}
ARITH = {"matmul_inputs": "float32", "storage": "float32", "bfp8_block": 32,
         "bfp8_edges": []}
B = 2
#: lossless plans: the order of float32 sums alone
TOL = 1e-5
SKIP = ("act_5", "concat_23")           # the level-1 long skip


@pytest.fixture(scope="module")
def case():
    net = reference.model_layers({"model": "unet2d", "model_kwargs": KW})
    kw, kx = jax.random.split(reference.seed_key(2 ** 33 + 16))
    weights = reference.make_weights(net, kw)
    frames = reference.make_frames(net, kx, (B,), 3)
    return net, weights, frames


def _graph():
    return build_unet(**{k: tuple(v) if isinstance(v, list) else v
                         for k, v in KW.items()})


def _plan(g, stage_starts=(), evict=()):
    """A manual plan: a new stage begins at each vertex of
    ``stage_starts``; the edges of ``evict`` are BFP8-evicted."""
    topo = g.topo()
    stage, s = {}, 0
    for n in topo:
        s += n in stage_starts
        stage[n] = s
    return ExecutionPlan(
        model=g.name, device="tpu_v5e_kernel", n_stages=s + 1,
        layers={n: LayerPlan(name=n, stage=stage[n]) for n in topo},
        streams=[StreamPlan(e.src, e.dst, evicted=(e.src, e.dst) in evict,
                            codec="bfp8" if (e.src, e.dst) in evict
                            else "none") for e in g.edges()],
        topo_order=topo)


def _compile(net, weights, plan=None):
    g = _graph()
    spec = repro.CompileSpec(
        model=g, device="tpu_v5e_kernel", mode="pipelined",
        kernel_mode="pallas", microbatches=B,
        strategy="dse" if plan is None else "manual-plan",
        plan=None if plan is None else plan(g))
    c = repro.compile(spec)
    theirs = {n: tuple(p.shape) for n, p in c.executor.params.items()}
    assert theirs == reference.weight_shapes(net)
    c.executor.params = dict(weights)
    return c


def _rel_l2(net, weights, frames, ys, arith):
    out = []
    for x, y in zip(frames, ys):
        ref = reference.forward(net, weights, x, arith)
        out.append(float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref)))
    return out


def test_dse_plan_matches_reference(case):
    """The DSE's plan (one stage at this size), the RGB stem with its nine
    taps in one dot (XLA's im2col), every other k x k conv through the
    line-buffer kernel, a row of three taps a dot."""
    net, weights, frames = case
    c = _compile(net, weights)
    assert c.plan.n_stages == 1
    assert c.input_shape() == (32, 48, 3)
    ys = c.run(frames)
    assert ys.shape == (B, 32 * 48 * 32)
    assert max(_rel_l2(net, weights, frames, ys, ARITH)) < TOL
    lb = c.report()["line_buffers"]
    assert (lb["conv_2"]["kernel"], lb["conv_2"]["taps_per_dot"],
            lb["conv_2"]["dot_depth"]) == ("xla_im2col", 9, 27)  # 3 x 3 x 3
    assert all((r["kernel"], r["taps_per_dot"]) == ("smof_conv_kxk", 3)
               for n, r in lb.items() if n != "conv_2")
    r = lb["conv_4"]                                  # 32 x 48, 32 -> 32
    assert (r["halo_rows"], r["eq1_depth_words"]) == (2, 3 * 48 * 32)
    assert r["halo_bytes_per_frame"] == (
        -(-32 // r["rows_per_block"]) * 2 * 48 * 32 * 4)


def test_three_stage_plan_matches_reference(case):
    """Three stages cut as the published UNet's plan is (after the last
    encoder pool, and inside the decoder): the skips and the cut edges
    cross stages as raw float32 stripes through the scan's carry."""
    net, weights, frames = case
    c = _compile(net, weights,
                 lambda g: _plan(g, stage_starts=("conv_12", "act_21")))
    assert c.plan.n_stages == 3
    crossing = {k for k, r in c.report()["traffic"]["hop"].items()}
    assert {"act_5->concat_23", "act_10->concat_17", "pool_11->conv_12",
            "conv_20->act_21"} <= crossing
    ys = c.run(frames)
    assert max(_rel_l2(net, weights, frames, ys, ARITH)) < TOL


def test_bfp8_evicted_skip_matches_reference(case):
    """One long skip evicted with BFP8: the act kernel encodes it, the
    concat decodes it standalone, and the reference runs that edge
    through the format.  Where a value lies on a rounding boundary the
    program's codec (``exp2``) may round a mantissa otherwise than the
    reference's (``ldexp``) on a CPU, one step of 2**-6 of its block's
    scale; hence 1e-4."""
    net, weights, frames = case
    c = _compile(net, weights, lambda g: _plan(g, evict=(SKIP,)))
    assert [s.codec for s in c.plan.streams if s.evicted] == ["bfp8"]
    ys = c.run(frames)
    arith = dict(ARITH, bfp8_edges=[list(SKIP)])
    assert max(_rel_l2(net, weights, frames, ys, arith)) < 1e-4
    # and the codec is real: without it the reference reads far off
    assert min(_rel_l2(net, weights, frames, ys, ARITH)) > 1e-3


def test_single_frame_run_and_trace(case):
    """``Compiled.run`` and ``Compiled.trace`` take one ``(H, W, C)``
    frame, broadcast through the pipeline."""
    net, weights, frames = case
    c = _compile(net, weights, lambda g: _plan(g))
    y1 = c.run(frames[0])
    ys = c.run(frames)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(ys[0]))
    yt, mc = c.trace(frames[0])
    assert mc is not None
    np.testing.assert_array_equal(np.asarray(yt[0]), np.asarray(ys[0]))


def test_fragmented_kxk_weight_is_refused(case):
    """Streaming part of a 3x3 conv's weight is not executed yet."""
    net, weights, frames = case
    g = _graph()
    plan = _plan(g)
    plan.layers["conv_4"].weight_static_fraction = 0.5
    c = repro.compile(repro.CompileSpec(
        model=g, device="tpu_v5e_kernel", mode="staged",
        kernel_mode="pallas", strategy="manual-plan", plan=plan))
    with pytest.raises(NotImplementedError, match="ROADMAP B1"):
        c.run(frames[0])
