"""Operations and bytes from layer shapes, against counts made by hand."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, work  # noqa: E402


def _net(name: str) -> list[dict]:
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    return reference.model_layers(cfg)


@pytest.mark.parametrize("name,macs", [("unet368", 54_987_325_440),
                                       ("yolov8n_neck", 701_235_200)])
def test_frame_macs(name, macs):
    assert work.frame_macs(_net(name)) == macs
    assert work.frame_flops(_net(name)) == 2 * macs


def test_unet368_convs_are_memory_bound_on_a_v5e():
    # 1.50 GB of float32 conv input, output and weights per frame at
    # 819 GB/s: 1.83 ms, against 0.56 ms for 110 GFLOP at 197 TFLOP/s
    net = _net("unet368")
    total = sum(c["bytes"] for c in work.convs(net))
    assert 1.49e9 < total < 1.51e9
    t, bound = work.conv_least_time(net, 197e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(total / 819e9)


def test_unet368_names_the_long_skips_as_the_program_does():
    net = {L["name"]: L for L in _net("unet368")}
    for skip, concat in (("act_3", "concat_33"), ("act_6", "concat_28"),
                         ("act_9", "concat_23")):
        assert net[concat]["inputs"][0] == skip
    assert len(reference.weight_shapes(list(net.values()))) == 14


@pytest.mark.parametrize("name", ["unet368", "yolov8n_neck"])
def test_reference_layers_are_the_programs_graph(name):
    """The plain description names, orders and sizes every vertex as the
    program's builder does, so weights and plans can be named alike."""
    from bench import system
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    g = system.graph(cfg)
    net = reference.model_layers(cfg)
    assert sorted(L["name"] for L in net) == sorted(g.topo())
    for L in net:
        v = g.vertex(L["name"])
        spec = v.meta["exec"]
        assert v.kind == L["kind"]
        assert [e.src for e in g.in_edges(L["name"])] == L["inputs"]
        assert (spec["cin"], spec["cout"], spec["m"],
                spec.get("m_out", spec["m"])) == (
            L["cin"], L["cout"], L["m"], L["m_out"])
