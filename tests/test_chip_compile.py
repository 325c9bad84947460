"""Compile-only rehearsal of the main path's Pallas kernels for a TPU v5e.

Nothing runs here: every kernel is lowered and compiled by the TPU compiler
for a described ``v5e:2x2`` topology (no chip attached), at the widths
``chip_smoke.py`` drives — the UNet frame of 368x480 positions and its
64..1024 channel ladder — and the published 2-D UNet's k x k convs.  Interpret mode cannot show what Mosaic refuses
(unsupported reshapes, value-level dynamic slices, unaligned tiles); this
file does, at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import bfp8, streaming_conv as SC
from repro.kernels.streamed_matmul import streamed_matmul_padded

POSITIONS = 368 * 480        # the UNet frame, flattened
BLOCK = 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_of(topo):
    """``shape_of(shape, dtype)`` -> a ShapeDtypeStruct on chip 0."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    yield make
    jax.config.update("jax_enable_compilation_cache", prev)


def _payload(shape_of, m, c):
    return (shape_of((m, c), jnp.int8), shape_of((m, c // BLOCK), jnp.int8))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (m, cin, cout): the first encoder level at full resolution, and the
# deepest level (four halvings) at the widest channels
CONV_CASES = [(POSITIONS, 64, 128), (POSITIONS // 16, 512, 1024)]


@pytest.mark.parametrize("m,cin,cout", CONV_CASES)
@pytest.mark.parametrize("variant", ["plain", "egress", "ingress", "both"])
def test_conv2d_compiles(shape_of, variant, m, cin, cout):
    ingress = variant in ("ingress", "both")
    encode = variant in ("egress", "both")
    w = shape_of((cin, cout))
    if ingress:
        _compile(lambda man, exp, w: SC.conv2d(None, w, payload=(man, exp),
                                                encode=encode),
                 *_payload(shape_of, m, cin), w)
    else:
        _compile(lambda x, w: SC.conv2d(x, w, encode=encode),
                 shape_of((m, cin)), w)


# (h, w, cin, cout): the published UNet's first level at full resolution,
# and its widest conv four pools down (W = 60 walks rows padded to 64)
KXK_CASES = [(368, 480, 64, 64), (46, 60, 512, 1024)]


@pytest.mark.parametrize("h,w,cin,cout", KXK_CASES)
def test_conv_kxk_compiles(shape_of, h, w, cin, cout):
    _compile(lambda x, wt: SC.conv_kxk(x, wt, hw=(h, w)),
             shape_of((h * w, cin)), shape_of((3, 3, cin, cout)))


def test_conv2d_normalised_tile_compiles(shape_of):
    """A tile request Mosaic would refuse (bm=5, bc=7) is normalised to one
    it accepts (8 rows, the full 128-lane-multiple channel block)."""
    _compile(lambda x, w: SC.conv2d(x, w, bm=5, bc=7),
             shape_of((4096, 64)), shape_of((64, 256)))


# dwconv keeps its input un-blocked in VMEM (halo reads), so it is
# rehearsed on a temporal stripe that fits there
DW_M, DW_C = 4096, 128


@pytest.mark.parametrize("variant", ["plain", "egress", "ingress"])
def test_dwconv_compiles(shape_of, variant):
    w = shape_of((3, DW_C))
    if variant == "ingress":
        _compile(lambda man, exp, w: SC.dwconv(None, w, payload=(man, exp)),
                 *_payload(shape_of, DW_M, DW_C), w)
    else:
        _compile(lambda x, w: SC.dwconv(x, w, encode=variant == "egress"),
                 shape_of((DW_M, DW_C)), w)


def test_pool_egress_compiles(shape_of):
    _compile(lambda x: SC.pool(x, POSITIONS // 2, encode=True),
             shape_of((POSITIONS, 64)))


@pytest.mark.parametrize("variant", ["egress", "ingress"])
def test_act_relu_compiles(shape_of, variant):
    m, c = POSITIONS, 64
    if variant == "ingress":
        _compile(lambda man, exp: SC.act_relu(None, c=c, payload=(man, exp)),
                 *_payload(shape_of, m, c))
    else:
        _compile(lambda x: SC.act_relu(x, encode=True), shape_of((m, c)))


# the skip three halvings down: 22080 rows, not a multiple of the stripe
@pytest.mark.parametrize("c", [64, 512])
def test_bfp8_quant_compiles(shape_of, c):
    _compile(lambda x: bfp8.bfp8_quant(x, block=BLOCK),
             shape_of((POSITIONS // 8, c)))


@pytest.mark.parametrize("c", [64, 512])
def test_bfp8_dequant_compiles(shape_of, c):
    _compile(lambda man, exp: bfp8.bfp8_dequant(man, exp, block=BLOCK),
             *_payload(shape_of, POSITIONS // 8, c))


def test_streamed_matmul_padded_compiles(shape_of):
    _compile(lambda x, w: streamed_matmul_padded(x, w, static_fraction=0.5),
             shape_of((POSITIONS // 16, 512)), shape_of((512, 1024)))


def _one_skip_plan(g):
    """A one-stage plan of ``g`` that evicts its first long skip (an act's
    edge to a concat) BFP8-compressed and nothing else."""
    from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan
    g.compute_buffer_depths()
    skip = next((e.src, e.dst) for e in g.edges()
                if g.vertex(e.src).kind == "act"
                and g.vertex(e.dst).kind == "concat")
    return ExecutionPlan(
        model=g.name, device="tpu_v5e_kernel", n_stages=1,
        layers={n: LayerPlan(name=n) for n in g.topo()},
        streams=[StreamPlan(e.src, e.dst, evicted=(e.src, e.dst) == skip,
                            codec="bfp8" if (e.src, e.dst) == skip else "none")
                 for e in g.edges()],
        topo_order=g.topo())


def test_scopes_leave_the_pipelined_step_unchanged(shape_of, monkeypatch):
    """The device scopes are metadata: the pipelined step of a small UNet
    with one BFP8-evicted skip compiles to the same instructions with the
    scope helper a no-op, once metadata and kernel names are stripped, and
    every Pallas kernel of it carries an ``smof_`` name.  The hop is the
    identity here (the step is lowered on a host without a chip, and the
    lowering asks the host's devices for host memory)."""
    import contextlib
    import re

    from _hlo import instructions_only
    from repro.core import build_unet_exec
    from repro.core.builders import exec_input_shape
    from repro.runtime import executor
    from repro.runtime.streamer import lower_plan_pipelined, pipeline

    g = build_unet_exec(positions=POSITIONS // 16, levels=2)
    plan = _one_skip_plan(g)

    def step_text() -> str:
        sx = lower_plan_pipelined(g, plan, microbatches=2,
                                  kernel_mode="pallas", interpret=False)
        params = {k: shape_of(v.shape) for k, v in sx.params.items()}
        xs = shape_of((2,) + exec_input_shape(g))
        return sx.fn.lower(params, xs).compile().as_text()

    scoped = step_text()
    for mod in (executor, pipeline):
        monkeypatch.setattr(mod, "scope",
                            lambda kind, name=None: contextlib.nullcontext())
    bare = step_text()
    assert "smof.emit" in scoped and "smof.codec.dec:" in scoped
    assert "smof." not in bare
    assert instructions_only(scoped) == instructions_only(bare)
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                         r'"tpu_custom_call"', scoped)
    assert kernels and all(k.startswith("smof_") for k in kernels), kernels


def test_report_counts_the_hops_host_link_bytes():
    """The small UNet's one BFP8-evicted skip is a (11040, 32) stripe: the
    hop moves its int8 mantissas (11040, 32) and exponents (11040, 1), both
    packed lane-dense.  A tile of the int8 layout is 32 rows x 128 lanes."""
    from repro.core import build_unet_exec
    from repro.runtime.executor import lower_plan
    from repro.runtime.streamer import lower_plan_pipelined

    g = build_unet_exec(positions=POSITIONS // 16, levels=2)
    plan = _one_skip_plan(g)
    unpacked = 11040 * 128 + 11040 * 128       # each array's lanes padded
    packed = (2784 * 128                       # 353,280 B = 2760 rows of 128
              + 96 * 128)                      # 11,040 B = 87 rows of 128
    for ex in (lower_plan(g, plan, kernel_mode="pallas"),
               lower_plan_pipelined(g, plan, microbatches=2,
                                    kernel_mode="pallas")):
        s = ex.report.summary()
        assert (s["hop_arrays"], s["hop_repacked"]) == (2, 2)
        assert s["host_link_bytes"] == packed == 368_640
        assert s["host_link_bytes_unpacked"] == unpacked == 2_826_240
        [edge] = s["hop"].values()
        assert edge == {"arrays": 2, "repacked": 2, "link_bytes": packed,
                        "link_bytes_unpacked": unpacked}


def test_pipelined_step_sends_lane_dense_arrays_to_host(topo, shape_of,
                                                        monkeypatch):
    """Compiled for the chip with the hop real, the small UNet's step moves
    each array of the evicted payload to host memory and back (two copies
    each), every one of them with a minor dimension of whole 128-lane rows.
    The host copies are what XLA keeps: a hop it folds away never leaves
    HBM."""
    import re

    from repro.core import build_unet_exec
    from repro.core.builders import exec_input_shape
    from repro.runtime.streamer import lower_plan_pipelined

    g = build_unet_exec(positions=POSITIONS // 16, levels=2)
    plan = _one_skip_plan(g)
    # the hop asks jax.devices() for a TPU with host memory
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    sx = lower_plan_pipelined(g, plan, microbatches=2, kernel_mode="pallas",
                              interpret=False)
    monkeypatch.undo()
    params = {k: shape_of(v.shape) for k, v in sx.params.items()}
    xs = shape_of((2,) + exec_input_shape(g))
    text = sx.fn.lower(params, xs).compile().as_text()
    # a copy-start's tuple is (destination, source, context), each an
    # array with its layout; host memory is the layout's S(5)
    copies = [re.findall(r"(\w+\[[\d,]+\])\{([^}]*)\}", line)[:2]
              for line in text.splitlines() if " copy-start(" in line]
    to_host = [a for (a, dst), _ in copies if "S(5)" in dst]
    to_device = [a for _, (a, src) in copies if "S(5)" in src]
    assert len(to_host) == len(to_device) == sx.report.summary()["hop_arrays"]
    assert all(int(a.rstrip("]").split(",")[-1]) % 128 == 0
               for a in to_host + to_device), to_host


def test_pipelined_step_stacks_its_output_in_whole_tiles(shape_of):
    """Compiled for the chip, the scan of the small UNet's step stacks each
    tick's output into a rank-3 buffer of 128-lane rows, ``(ticks, n,
    128)``: a tick's write covers whole (8, 128) tiles of its own.  A
    rank-2 ``(ticks, L)`` buffer would put 8 ticks in each tile, so that
    every tick's write reads and writes the whole buffer."""
    import re

    from repro.core import build_unet_exec
    from repro.core.builders import exec_input_shape
    from repro.runtime.streamer import lower_plan_pipelined

    g = build_unet_exec(positions=POSITIONS // 16, levels=2)
    sx = lower_plan_pipelined(g, _one_skip_plan(g), microbatches=2,
                              kernel_mode="pallas", interpret=False)
    params = {k: shape_of(v.shape) for k, v in sx.params.items()}
    xs = shape_of((2,) + exec_input_shape(g))
    text = sx.fn.lower(params, xs).compile().as_text()
    stacked = re.findall(
        r"= f32\[([\d,]+)\]\{[^}]*\} dynamic-update-slice\([^\n]*"
        r'op_name="jit\(step\)/smof\.emit/while/body/', text)
    assert stacked, "no stacking update in the step's scan"
    shapes = [tuple(map(int, s.split(","))) for s in stacked]
    assert all(len(s) == 3 and s[-1] == 128 for s in shapes), shapes
    assert shapes[0] == sx.report.emit_rows
