"""The program's device scopes, and the readers that partition a step by
them (``bench/scopes.py``).

The compiles build small ``unet`` and ``yolov8n_neck`` configurations
through ``bench/system.py`` on the CPU, the Pallas kernels in interpret
mode, and read the compiled step's HLO as the harness does.  The DSE
evicts nothing at these sizes for the cells' on-chip view, so the UNet is
also compiled for a view with 64 kbit on chip (BFP8-evicted skips) and one
with 16 kbit (several stages, so the ticks carry crossings).
"""
from __future__ import annotations

import copy
import dataclasses
import json
import pathlib
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))        # the program, as system.py does

from bench import ops, reference, run, scopes, system, trace_reduce  # noqa: E402
from bench.tests.test_trace_reduce import _plane  # noqa: E402

OP_NAME = re.compile(r'op_name="([^"]*)"')
SMALL = {
    "unet368": {"positions": 256, "cin": 32, "base": 32, "levels": 3,
                "n_classes": 32},
    "yolov8n_neck": {"positions": 256, "widths": [32, 64, 128], "head": 32},
}


def _compile(name: str, onchip_kbit: float | None, monkeypatch):
    """The compiled artifact and its step's HLO for a small configuration,
    with the device view's on-chip storage set to ``onchip_kbit``."""
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    cfg["model_kwargs"] = copy.deepcopy(SMALL[name])
    cfg["system"] = dict(cfg["system"], microbatches=2)
    if onchip_kbit is not None:
        import repro.core.resources as resources
        view = resources.get_device(cfg["system"]["device_view"])
        monkeypatch.setattr(resources, "get_device", lambda _: dataclasses
                            .replace(view, onchip_bits=onchip_kbit * 1e3))
    net = reference.model_layers(cfg)
    c = system.build(cfg, reference.make_weights(net, jax.random.PRNGKey(0)))
    return c, system.step_hlo(c, net)


def _scopes(text: str) -> set[str]:
    return {part for name in OP_NAME.findall(text)
            for part in name.split("/") if part.startswith("smof.")}


def _lowered(c) -> str:
    m, cin = c.input_shape()
    xs = jax.ShapeDtypeStruct((c.executor.microbatches, m, cin), jnp.float32)
    return c.executor.fn.lower(c.executor.params, xs).as_text(debug_info=True)


def _in_lowered(label: str, lowered: str) -> bool:
    """Whether ``label`` is a whole scope of an op in the lowered text."""
    return re.search(rf'["/]{re.escape(label)}/', lowered) is not None


@pytest.mark.parametrize("name,onchip_kbit", [
    ("unet368", 64), ("unet368", 16), ("yolov8n_neck", None)])
def test_the_step_names_every_vertex_codec_call_and_tick(
        name, onchip_kbit, monkeypatch):
    from repro.runtime.executor import (_lower_vertex, analyze_plan,
                                        vertex_body)
    c, hlo = _compile(name, onchip_kbit, monkeypatch)
    g, an = c.graph, c.executor
    found = _scopes(hlo)
    lowered = _lowered(c)
    for v in g.vertices():
        label = f"smof.{v.kind}:{v.name}"
        if v.kind == "input":                # the frame itself: no op
            continue
        assert _in_lowered(label, lowered), label
        # an output of one input is a reshape, which compiles to no op
        if not (v.kind == "output" and len(g.in_edges(v.name)) == 1):
            assert label in found, (label, sorted(found))
    # each standalone codec call: a decode where the consumer does not
    # fuse it, an encode where the producer does not
    evicted = [(s.src, s.dst) for s in c.plan.streams
               if s.evicted and s.codec == "bfp8"]
    assert bool(evicted) == (onchip_kbit == 64)
    plan_an = analyze_plan(g, c.plan, use_pallas=True, interpret=True)
    for src, dst in evicted:
        if _lower_vertex(g, dst, plan_an).fuse_in != (src, dst):
            assert f"smof.codec.dec:{src}-{dst}" in found
        if not _lower_vertex(g, src, plan_an).fuse_out:
            assert f"smof.codec.enc:{src}" in found
    assert {"smof.tick", "smof.tick.read", "smof.emit"} <= found
    assert not any(s.startswith("smof.hop") for s in found)
    # a delay line of one tick compiles to no op of its own
    assert _in_lowered("smof.tick.carry", lowered) == (an.n_stages > 1)
    # a vertex with a Pallas body runs a kernel named for its kind (in
    # interpret mode the name is a scope of the kernel's own ops)
    names = OP_NAME.findall(hlo)
    for v in g.vertices():
        if vertex_body(g, v.name, plan_an) == "pallas":
            kernel = f"smof.{v.kind}:{v.name}/smof_"
            assert any(kernel in n for n in names), kernel


# -- the readers on synthetic reductions -------------------------------------

def _line(name: str, result: str, opcode: str, operands: str = "",
          op_name: str | None = None, extra: str = "") -> str:
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"%{name} = {result} {opcode}({operands}){extra}{meta}"


BODY = "jit(step)/smof.emit/while/body/closed_call/smof.tick"
LINES = {   # name -> (line, device ns per call), one call per 100 frames
    "smof_conv.1": (_line(
        "smof_conv.1", "f32[64,256]{1,0}", "custom-call",
        "f32[64,128]{1,0} %a, f32[128,256]{1,0} %w",
        f"{BODY}/smof.conv:conv_2/smof_conv/pallas_call",
        ', custom_call_target="tpu_custom_call"'), 400),
    "smof_act.2": (_line(
        "smof_act.2", "f32[64,256]{1,0}", "custom-call",
        "f32[64,256]{1,0} %b", f"{BODY}/smof.act:act_3/smof_act/pallas_call",
        ', custom_call_target="tpu_custom_call"'), 200),
    "fusion.3": (_line(
        "fusion.3", "f32[32,256]{1,0}", "fusion", "f32[64,256]{1,0} %c",
        f"{BODY}/smof.pool:pool_4/reduce_sum",
        ", kind=kLoop, calls=%fused_computation.3"), 50),
    "fusion.4": (_line(
        "fusion.4", "f32[64,256]{1,0}", "fusion", "f32[32,256]{1,0} %d",
        f"{BODY}/smof.upsample:upsample_5/broadcast_in_dim",
        ", kind=kLoop, calls=%fused_computation.4"), 30),
    "slice.5": (_line(
        "slice.5", "f32[60,256]{1,0}", "slice", "f32[64,256]{1,0} %e",
        f"{BODY}/smof.conv:conv_2/slice", ", slice={[0:60], [0:256]}"), 20),
    "copy-start.6": (_line(
        "copy-start.6", "(f32[128,256]{1,0:S(1)}, f32[128,256]{1,0}, u32[])",
        "copy-start", "f32[128,256]{1,0} %w"), 5),
    "dynamic-update-slice.7": (_line(
        "dynamic-update-slice.7", "f32[2,8192]{1,0}", "dynamic-update-slice",
        "f32[2,8192]{1,0} %o, f32[1,8192]{1,0} %y, s32[] %i, s32[] %j",
        "jit(step)/smof.emit/while/body/dynamic_update_slice"), 60),
    "smof_bfp8_dequant.8": (_line(
        "smof_bfp8_dequant.8", "f32[64,256]{1,0}", "custom-call",
        "s8[64,256]{1,0} %m, s8[64,8]{1,0} %x",
        f"{BODY}/smof.codec.dec:act_3-concat_9/smof_bfp8_dequant/"
        "pallas_call", ', custom_call_target="tpu_custom_call"'), 25),
    "copy-done.9": (_line(
        "copy-done.9", "s8[64,8]{1,0:S(5)}", "copy-done",
        "(s8[64,8]{1,0:S(5)}, s8[64,8]{1,0}, u32[]) %cs"), 70),
    "fusion.10": (_line(
        "fusion.10", "f32[64,256]{1,0}", "fusion", "f32[64,256]{1,0} %f",
        f"{BODY}/smof.add:add_6/add",
        ", kind=kLoop, calls=%fused_computation.10"), 15),
    "fusion.11": (_line(
        "fusion.11", "f32[64]{0}", "fusion", "f32[64,256]{1,0} %g",
        "jit(step)/reduce_max", ", kind=kLoop, calls=%fused_computation.11"),
        10),
    "broadcast.12": (_line(     # the tick's zeros: glue, not output assembly
        "broadcast.12", "f32[8192]{0}", "broadcast", "f32[] %z",
        "jit(step)/smof.emit/while/body/closed_call/smof.tick/"
        "broadcast_in_dim"), 4),
}
CLASS = {"smof_conv.1": "conv", "smof_act.2": "eltwise",
         "fusion.3": "eltwise", "fusion.4": "glue", "slice.5": "glue",
         "copy-start.6": "glue", "dynamic-update-slice.7": "emit",
         "smof_bfp8_dequant.8": "codec", "copy-done.9": "hop",
         "fusion.10": "eltwise", "fusion.11": "other", "broadcast.12": "glue"}


def _reduction(lines: dict) -> trace_reduce.Reduction:
    """Two calls of the step in a 2000 ns window, each running every op of
    ``lines`` in turn."""
    host = _plane(1, "/host:CPU", "python", [("traced", 0, 2000)])
    events, modules = [], []
    for call in range(2):
        t = 1000 * call
        modules.append(("jit_step(1)", t, 1000))
        for line, ns in lines.values():     # quoted for the text proto
            events.append((line.replace('"', '\\"'), t, ns))
            t += ns
    dev = _plane(2, "/device:TPU:0", "XLA Ops", events,
                 more={"XLA Modules": modules})
    pd = ProfileData.from_text_proto(host + dev)
    text = "\n".join(line for line, _ in lines.values())
    return trace_reduce.reduce_profile(pd, ops.index(text))


def _read(metric: str, red, frames: float = 200.0) -> float | None:
    reader = run.load_module(ROOT / "bench" / "metrics" / f"{metric}.py",
                             f"bench_metric_{metric}")
    return reader.read(types.SimpleNamespace(trace=red, frames=frames))


def test_each_op_falls_in_one_class():
    red = _reduction(LINES)
    by_name = {o.name.split(" ")[0].lstrip("%"): o for o in red.ops[0]}
    assert {n: scopes.classify(by_name[n]) for n in LINES} == CLASS
    assert scopes.scope(by_name["smof_conv.1"]) == "smof.conv:conv_2"
    assert scopes.kind(by_name["smof_bfp8_dequant.8"]) == "codec.dec"
    assert scopes.scope(by_name["copy-start.6"]) is None


@pytest.mark.parametrize("metric,cls", [("emit_ms.stream", "emit"),
                                        ("eltwise_ms.stream", "eltwise"),
                                        ("glue_ms.stream", "glue")])
def test_readers_give_device_ms_per_frame_of_their_class(metric, cls):
    red = _reduction(LINES)
    ns = sum(ns for n, (_, ns) in LINES.items() if CLASS[n] == cls)
    # two calls of ``ns`` each over 200 frames
    assert _read(metric, red) == pytest.approx(1e-6 * 2 * ns / 200)


def test_the_classes_partition_the_ops_time():
    red = _reduction(LINES)
    parts = scopes.partition(red, frames=200.0)
    total = 1e-6 * 2 * sum(ns for _, ns in LINES.values()) / 200
    assert sum(ms for ms, _ in parts.values()) == pytest.approx(total)
    assert parts["glue"][1][0] == ("smof.upsample:upsample_5",
                                   pytest.approx(1e-6 * 2 * 30 / 200))
    assert dict(parts["glue"][1])["(none)"] == pytest.approx(1e-6 * 2 * 5
                                                             / 200)


@pytest.mark.parametrize("metric", ["emit_ms.stream", "eltwise_ms.stream",
                                    "glue_ms.stream"])
def test_a_program_without_scopes_reads_nothing(metric):
    bare = {n: (OP_NAME.sub('op_name="jit(step)/while/body/x"', line), ns)
            for n, (line, ns) in LINES.items()}
    assert _read(metric, _reduction(bare)) is None


def test_a_recorded_scoped_v5e_trace():
    """``yolov8n_neck.stream`` traced on a TPU v5e for 0.05 s with the
    scoped program (``data/yolov8n_neck.stream.v5e.*``: the trace and the
    step's HLO, kept by ``bench/run.py --keep-trace`` from a run whose
    traffic traced 0.05 s in place of 2 s).  Every op belongs to a scope or
    is one of XLA's copies; each kernel is named; the output buffer's
    ``dynamic-update-slice`` is the output assembly, 40% of the step."""
    import gzip
    data = ROOT / "bench" / "tests" / "data"
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        (data / "yolov8n_neck.stream.v5e.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((data / "yolov8n_neck.stream.v5e.hlo.txt.gz")
                          .read_bytes()).decode()
    red = trace_reduce.reduce_profile(pd, ops.index(hlo))
    frames = red.step_calls() * 8
    assert frames == pytest.approx(264, rel=1e-3)
    live = [o for o in red.ops[0] if not o.container]
    assert all(scopes.scope(o) is not None
               or ops.instr(o).opcode in ("copy", "copy-start", "copy-done")
               for o in live)
    assert all(o.name.startswith("%smof_") for o in live
               if ops.is_pallas(ops.instr(o)))
    assert not any("smof.hop" in (scopes.op_name(o) or "") for o in live)
    assert any("/smof.tick/" in (scopes.op_name(o) or "") for o in live)
    emit = _read("emit_ms.stream", red, frames)
    assert emit == pytest.approx(0.0753, rel=0.01)     # ms per frame
    busy = 1e3 * red.busy_s / frames
    assert emit / busy == pytest.approx(0.40, abs=0.01)
    parts = scopes.partition(red, frames)
    assert parts["emit"][0] == pytest.approx(emit)
    assert sum(ms for ms, _ in parts.values()) == pytest.approx(busy,
                                                                rel=0.01)
