"""Which ops implement which work, on the compiled steps of both cells.

``data/<config>.step.hlo.txt.gz`` is the HLO text of each configuration's
pipelined B=8 step as the TPU compiler built it for a v5e at the cells'
sizes (kernel bodies left out), with the off-chip hop of the TPU path.
"""
from __future__ import annotations

import gzip
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import ops  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _ops(config: str) -> list:
    text = gzip.decompress((DATA / f"{config}.step.hlo.txt.gz").read_bytes())
    return [types.SimpleNamespace(instr=i, hlo="")
            for i in ops.index(text.decode()).values()]


@pytest.mark.parametrize("config,conv,bfp8,hop", [
    # unet368: 8 convs as Pallas kernels (K and W split into the static and
    # streamed fragments) and 6 as XLA dot fusions (K <= 128): 14 weights;
    # 3 standalone dequants at the concats (the quants are fused into the
    # act kernels); 3 skips x (mantissas, exponents) x (to host, back) x
    # (start, done) = 24 host copies
    ("unet368", 14, 3, 24),
    # yolov8n_neck: 13 convs, all Pallas kernels; nothing evicted
    ("yolov8n_neck", 13, 0, 0),
])
def test_op_classes_of_the_compiled_step(config, conv, bfp8, hop):
    got = _ops(config)
    assert sum(map(ops.is_conv, got)) == conv
    assert sum(map(ops.is_bfp8, got)) == bfp8
    assert sum(map(ops.is_hop, got)) == hop


def test_copies_to_vmem_are_not_the_hop():
    copies = [o for o in _ops("yolov8n_neck")
              if o.instr.opcode in ("copy-start", "copy-done")]
    assert copies and not any(map(ops.is_hop, copies))


def test_act_kernel_with_fused_egress_is_neither_conv_nor_standalone():
    # act_3/6/9 return the activation and its BFP8 payload
    fused = [o for o in _ops("unet368")
             if o.instr.results == ("f32", "s8", "s8")]
    assert len(fused) == 3
    assert not any(map(ops.is_conv, fused))
    assert not any(map(ops.is_bfp8, fused))


@pytest.mark.parametrize("line,conv,bfp8", [
    # operand types printed inline, as a trace's own text may give them
    ('%closed_call.9 = f32[64,128]{1,0} custom-call(f32[64,32]{1,0} %a, '
     'f32[32,128]{1,0} %w), custom_call_target="tpu_custom_call"', True,
     False),
    ('%closed_call.7 = f32[64,128]{1,0} custom-call(s8[64,128]{1,0} %m, '
     's8[64,4]{1,0} %e), custom_call_target="tpu_custom_call"', False, True),
    ('%closed_call.8 = (s8[64,128]{1,0}, s8[64,4]{1,0}) custom-call('
     'f32[64,128]{1,0} %x), custom_call_target="tpu_custom_call"', False,
     True),
    ('%fusion.3 = f32[64,128]{1,0} fusion(f32[64,32]{1,0} %a), kind=kLoop, '
     'calls=%fused_computation.3', False, False),
])
def test_classes_from_a_traces_own_text(line, conv, bfp8):
    op = types.SimpleNamespace(instr=None, hlo=line)
    assert ops.is_conv(op) == conv
    assert ops.is_bfp8(op) == bfp8


@pytest.mark.parametrize("line,hop", [
    # a trace's copy-done back from host memory names the space only in
    # its inline operand, a tuple
    ('%copy-done.7 = s8[64,8]{1,0:T(8,128)(4,1)} copy-done((s8[64,8]{1,0:'
     'T(8,128)(4,1)}, s8[64,8]{1,0:T(8,128)(4,1)S(5)}, u32[]{:S(2)}) '
     '%copy-start.7)', True),
    ('%copy-done.2 = bf16[32,64]{1,0:T(8,128)(2,1)} copy-done((bf16[32,64]'
     '{1,0:T(8,128)(2,1)}, bf16[32,64]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) '
     '%copy-start.2)', False),
])
def test_host_copies_from_a_traces_own_text(line, hop):
    assert ops.is_hop(types.SimpleNamespace(instr=None, hlo=line)) == hop


def test_label_keeps_name_shapes_and_opcode():
    line = ('%fusion.64 = f32[88320,128]{1,0:T(8,128)S(1)} fusion('
            'f32[88320,64]{1,0:T(8,128)} %closed_call.74, bf16[64,128]{1,0} '
            '%get-tuple-element.297), kind=kOutput, calls=%fused_computation.25')
    assert ops.label(line) == ("%fusion.64 = f32[88320,128] fusion("
                               "f32[88320,64], bf16[64,128])")
    assert ops.label("an event that is no HLO line") == \
        "an event that is no HLO line"


@pytest.mark.parametrize("line,nbytes", [
    # result and first operand in HBM, second operand and weights in VMEM
    ('%closed_call.9 = f32[64,128]{1,0:T(8,128)} custom-call(f32[64,32]'
     '{1,0:T(8,128)} %a, f32[64,16]{1,0:T(8,128)S(1)} %b, f32[48,128]{1,0:'
     'T(8,128)S(1)} %w), custom_call_target="tpu_custom_call"',
     4 * (64 * 128 + 64 * 32)),
    ('%closed_call.7 = f32[64,128]{1,0} custom-call(s8[64,128]{1,0} %m, '
     's8[64,4]{1,0} %e), custom_call_target="tpu_custom_call"',
     4 * 64 * 128 + 64 * 128 + 64 * 4),
    # operand types not in the line: nothing to read
    ('%fusion.3 = f32[64,128]{1,0} fusion(%a, %w), kind=kOutput', None),
])
def test_hbm_bytes_from_a_traces_own_text(line, nbytes):
    assert ops.hbm_bytes(types.SimpleNamespace(hlo=line)) == nbytes
