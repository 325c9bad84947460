"""``kxk_roofline.stream``: the k x k convs' share of their roofline, read
from ops under those convs' ``smof.conv:<vertex>`` scopes.

A synthetic trace of a small 2-D UNet's step (``bench/tests/test_scopes``'s
helpers): the line-buffer kernel under ``conv_4`` and XLA's conv of the
stem under ``conv_2`` are counted; the 1x1 head's kernel under
``conv_28`` and a ReLU are not."""
from __future__ import annotations

import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, run, work  # noqa: E402
from bench.tests.test_scopes import BODY, OP_NAME, _line, _reduction  # noqa: E402

PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
NET = reference.model_layers({"model": "unet2d", "model_kwargs": {
    "input_hw": [32, 48], "cin": 3, "base": 32, "levels": 3,
    "n_classes": 32}})
TPU = ', custom_call_target="tpu_custom_call"'
LINES = {   # name -> (line, device ns per call), two calls in the trace
    "smof_conv_kxk.1": (_line(
        "smof_conv_kxk.1", "f32[1536,32]{1,0}", "custom-call",
        "f32[1632,32]{1,0} %x, f32[1632,32]{1,0} %x, bf16[3,96,32]{2,1,0} %w",
        f"{BODY}/smof.conv:conv_4/smof_conv_kxk/pallas_call", TPU), 300),
    "fusion.2": (_line(
        "fusion.2", "f32[1,32,48,32]{3,2,1,0}", "fusion",
        "bf16[1,32,48,3]{3,2,1,0} %f, bf16[3,3,3,32]{3,2,1,0} %s",
        f"{BODY}/smof.conv:conv_2/conv_general_dilated",
        ", kind=kOutput, calls=%fused_computation.2"), 100),
    "smof_conv.3": (_line(
        "smof_conv.3", "f32[1536,32]{1,0}", "custom-call",
        "f32[1536,32]{1,0} %a, f32[32,32]{1,0} %h",
        f"{BODY}/smof.conv:conv_28/smof_conv/pallas_call", TPU), 50),
    "smof_act.4": (_line(
        "smof_act.4", "f32[1536,32]{1,0}", "custom-call",
        "f32[1536,32]{1,0} %b", f"{BODY}/smof.act:act_5/smof_act/pallas_call",
        TPU), 40),
}


def _read(red, net=NET, frames: float = 2.0):
    reader = run.load_module(
        ROOT / "bench" / "metrics" / "kxk_roofline.stream.py",
        "bench_metric_kxk_roofline")
    return reader.read(types.SimpleNamespace(trace=red, frames=frames,
                                             net=net, peaks=PEAKS))


def test_reads_the_kxk_convs_against_their_own_work():
    red = _reduction(LINES)
    wide = {L["name"] for L in NET if L["kind"] == "conv" and L["k"] > 1}
    flops = 2.0 * sum(c["flops"] for c in work.convs(NET)   # two frames
                      if c["name"] in wide)
    hbm = 2 * (4 * (1536 * 32 + 2 * 1632 * 32) + 2 * 3 * 96 * 32
               + 4 * 32 * 48 * 32 + 2 * (32 * 48 * 3 + 27 * 32))
    busy = 2 * (300 + 100) * 1e-9
    least = max(flops / PEAKS["flops_per_s"], hbm / PEAKS["hbm_bytes_per_s"])
    assert _read(red) == pytest.approx(100 * least / busy)


def test_reads_nothing_without_spatial_convs_or_scopes():
    red = _reduction(LINES)
    flat = reference.model_layers({"model": "unet", "model_kwargs": {
        "positions": 256, "cin": 32, "base": 32, "levels": 3,
        "n_classes": 32}})
    assert _read(red, net=flat) is None
    bare = {n: (OP_NAME.sub('op_name="jit(step)/while/body/x"', line), ns)
            for n, (line, ns) in LINES.items()}
    assert _read(_reduction(bare)) is None
