"""The benchmark's configurations at full size, planned but not lowered.

Each configuration of ``bench/configs/`` is built from its ``system``
block as ``bench/system.py`` builds it and planned by the DSE through
``repro.build_plan`` under its device view: no weights are made and
nothing is traced.  The plan, what it sends over the host link, the
weights the program asks for and the body each vertex runs are what the
benchmark's cells measure; these tests pin them, so a change to the DSE
or to the lowering that changes a cell shows here, not first on the chip.
"""
from __future__ import annotations

import functools
import json
import math
import pathlib
import sys

import pytest

import repro
from repro.core.dse import DSEConfig
from repro.core.resources import get_device
from repro.runtime import executor as ex

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, system  # noqa: E402

CELLS = ("unet368", "yolov8n_neck", "unet2d")


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@functools.lru_cache(maxsize=None)
def _planned(name: str):
    """(graph, plan, analysis) of a configuration, as its cell compiles
    it: the graph of its ``system.builder``, the DSE's plan under the device
    view, and the executors' static analysis in the configured kernel
    mode."""
    cfg = _config(name)
    s = cfg["system"]
    g = system.graph(cfg)
    plan, _ = repro.build_plan(repro.CompileSpec(
        model=g, device=get_device(s["device_view"]), strategy="dse",
        mode="pipelined", kernel_mode=s["kernel_mode"],
        dse=DSEConfig(**system._tuples(s["dse"])),
        microbatches=s["microbatches"]), g)
    an = ex.analyze_plan(g, plan, use_pallas=s["kernel_mode"] == "pallas",
                         interpret=True)
    return g, plan, an


# =============================================================================
# The DSE's plan (PERF.md, section 4)
# =============================================================================

UNET368_WEIGHTS = (
    [f"conv_{i}" for i in (2, 5, 8, 11, 14, 19, 24, 29, 34, 36)]
    + [f"deconv_{i}" for i in (17, 22, 27, 32)])

PLANS = {
    "unet368": {
        "n_stages": 1, "stage_starts": [],
        "evicted": [("act_3", "concat_33", "bfp8"),
                    ("act_6", "concat_28", "bfp8"),
                    ("act_9", "concat_23", "bfp8")],
        "fragmented": UNET368_WEIGHTS, "crossings": []},
    "yolov8n_neck": {
        "n_stages": 1, "stage_starts": [], "evicted": [], "fragmented": [],
        "crossings": []},
    "unet2d": {
        "n_stages": 3, "stage_starts": ["conv_22", "act_37"], "evicted": [],
        "fragmented": [],
        "crossings": [("act_5", "concat_45"), ("act_10", "concat_39"),
                      ("act_15", "concat_33"), ("act_20", "concat_27"),
                      ("pool_21", "conv_22"), ("conv_36", "act_37")]},
}


@pytest.mark.parametrize("name", CELLS)
def test_dse_plans_each_configuration_as_its_cell_states(name):
    g, plan, _ = _planned(name)
    stage = {n: lp.stage for n, lp in plan.layers.items()}
    topo = g.topo()
    got = {
        "n_stages": plan.n_stages,
        "stage_starts": [b for a, b in zip(topo, topo[1:])
                         if stage[b] != stage[a]],
        "evicted": {(s.src, s.dst, s.codec) for s in plan.streams
                    if s.evicted},
        "fragmented": {n for n, lp in plan.layers.items()
                       if lp.weight_static_fraction < 1.0},
        "crossings": {(e.src, e.dst) for e in g.edges()
                      if stage[e.src] != stage[e.dst]},
    }
    want = PLANS[name]
    assert got == {k: want[k] if k in ("n_stages", "stage_starts")
                   else set(want[k]) for k in want}


# =============================================================================
# What the plan sends over the host link, per frame (report()["traffic"])
# =============================================================================

def _hop(arrays, repacked, link, unpacked):
    return {"arrays": arrays, "repacked": repacked, "link_bytes": link,
            "link_bytes_unpacked": unpacked}


TRAFFIC = {
    # three BFP8 skips: int8 mantissas and exponents each; every array
    # but the 64-lane mantissas of act_6 and act_9 is packed lane-dense
    "unet368": {
        "n_spilled_edges": 3, "spill_offchip_bits": 279_797_760,
        "streamed_weight_bits": 33_488_896, "static_weight_bits": 0,
        "hop_arrays": 6, "hop_repacked": 4,
        "host_link_bytes": 34_983_936,
        "host_link_bytes_unpacked": 84_787_200,
        "hop": {"act_3->concat_33": _hop(2, 2, 11_661_312, 45_219_840),
                "act_6->concat_28": _hop(2, 1, 11_661_312, 22_609_920),
                "act_9->concat_23": _hop(2, 1, 11_661_312, 16_957_440)}},
    "yolov8n_neck": {
        "n_spilled_edges": 0, "spill_offchip_bits": 0,
        "streamed_weight_bits": 0, "static_weight_bits": 4_325_376,
        "hop_arrays": 0, "hop_repacked": 0, "host_link_bytes": 0,
        "host_link_bytes_unpacked": 0, "hop": {}},
    # six raw float32 crossings; only act_5's 64 lanes are packed
    "unet2d": {
        "n_spilled_edges": 6, "spill_offchip_bits": 195_010_560,
        "streamed_weight_bits": 0, "static_weight_bits": 248_215_040,
        "hop_arrays": 6, "hop_repacked": 1,
        "host_link_bytes": 97_517_568,
        "host_link_bytes_unpacked": 142_737_408,
        "hop": {"act_5->concat_45": _hop(1, 1, 45_219_840, 90_439_680),
                "act_10->concat_39": _hop(1, 0, 22_609_920, 22_609_920),
                "act_15->concat_33": _hop(1, 0, 11_304_960, 11_304_960),
                "act_20->concat_27": _hop(1, 0, 5_652_480, 5_652_480),
                "pool_21->conv_22": _hop(1, 0, 1_425_408, 1_425_408),
                "conv_36->act_37": _hop(1, 0, 11_304_960, 11_304_960)}},
}


@pytest.mark.parametrize("name", CELLS)
def test_host_link_traffic_of_each_configurations_plan(name):
    _, _, an = _planned(name)
    summary = an.report().summary()
    summary.pop("total_offchip_bits")
    assert summary == TRAFFIC[name]


# =============================================================================
# The program's weights against the reference's, by name and shape
# =============================================================================

#: weights per configuration: unet2d's count is its published 31,026,880
WEIGHT_COUNTS = {"unet368": (14, 2_093_056), "yolov8n_neck": (13, 270_336),
                 "unet2d": (23, 31_026_880)}


@pytest.mark.parametrize("name", CELLS)
def test_program_weights_are_the_references_by_name_and_shape(name):
    """What ``bench/system.build`` checks on the chip before it hands the
    program the reference's weights, here from the shapes alone."""
    cfg = _config(name)
    g = system.graph(cfg)
    ours = {v.name: ex.weight_shape(v.kind, v.meta["exec"])
            for v in g.vertices()
            if v.kind in ex.WEIGHT_KINDS or v.kind in ex.TEMPORAL_KINDS}
    theirs = reference.weight_shapes(reference.model_layers(cfg))
    assert ours == theirs
    assert (len(ours), sum(math.prod(s) for s in ours.values())) \
        == WEIGHT_COUNTS[name]


# =============================================================================
# The body each vertex runs in the configured kernel mode
# =============================================================================

BODIES = {
    # fragmented weights with cin <= 128 are too small to split: plain dots
    "unet368": {
        "reference": ["input_1", "conv_2", "conv_5", "conv_8",
                      "upsample_16", "concat_18", "upsample_21", "concat_23",
                      "upsample_26", "concat_28", "upsample_31", "deconv_32",
                      "concat_33", "conv_34", "conv_36", "output_37"],
        "fuse_out": ["act_3", "act_6", "act_9"]},
    "yolov8n_neck": {
        "reference": ["input_1", "upsample_10", "concat_11", "upsample_13",
                      "concat_14", "concat_17", "concat_20", "output_31"],
        "fuse_out": []},
    # the RGB stem takes XLA's im2col; max pools, up-convs and the 1x1
    # head are XLA's; every other 3x3 conv is the line-buffer kernel
    "unet2d": {
        "reference": ["input_1", "conv_2", "pool_6", "pool_11", "pool_16",
                      "pool_21", "deconv_26", "concat_27", "deconv_32",
                      "concat_33", "deconv_38", "concat_39", "deconv_44",
                      "concat_45", "conv_50", "output_51"],
        "fuse_out": []},
}


@pytest.mark.parametrize("name", CELLS)
def test_each_vertex_runs_the_body_its_cell_measures(name):
    g, _, an = _planned(name)
    topo = g.topo()
    low = {n: ex._lower_vertex(g, n, an) for n in topo}
    got = {"reference": [n for n in topo
                         if ex.vertex_body(g, n, an) == "reference"],
           "fuse_out": [n for n in topo if low[n].fuse_out]}
    assert got == BODIES[name]
    # no cell's plan has a single BFP8 in-edge a kernel could decode
    assert not any(lv.fuse_in for lv in low.values())
    assert [n for n in topo if low[n].needs_payload] == got["fuse_out"]
