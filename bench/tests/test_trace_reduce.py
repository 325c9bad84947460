"""The reduction from a profiler trace to busy, idle and per-op time."""
from __future__ import annotations

import gzip
import pathlib
import sys

import pytest
from jax.profiler import ProfileData

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import ops, trace_reduce  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _plane(pid: int, name: str, line: str, events: list[tuple],
           more: dict | None = None) -> str:
    """A plane with the line ``line`` of ``events``, (name, start_ns,
    dur_ns[, hlo]), and the lines of ``more``, name -> events."""
    lines = {line: events, **(more or {})}
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = []
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        body = []
        for e in evs:
            stat = (f' stats {{ metadata_id: 1 str_value: "{e[3]}" }}'
                    if len(e) > 3 else "")
            body.append(f"events {{ metadata_id: {ids[e[0]]} offset_ps: "
                        f"{e[1] * 1000} duration_ps: {e[2] * 1000}{stat} }}")
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                   f'{" ".join(body)} }}')
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"{n}" }} }}' for n, i in ids.items())
    return (f'planes {{ id: {pid} name: "{name}" {" ".join(out)} {meta} '
            f'stat_metadata {{ key: 1 value {{ id: 1 name: "long_name" }} }} }}')


def _trace() -> trace_reduce.Reduction:
    # window 100..1100 ns; the host runs two calls and generates between
    host = _plane(1, "/host:CPU", "python", [
        ("traced", 100, 1000), ("run", 100, 400), ("gen", 500, 100),
        ("run", 600, 450), ("flush", 2000, 10)])
    dev = _plane(2, "/device:TPU:0", "XLA Ops", [
        ("fusion.1", 50, 150, "%fusion.1 = f32[8] fusion(), calls=dot"),
        ("copy-start.2", 180, 100, "%copy-start.2 = copy-start()"),
        ("fusion.1", 700, 200, "%fusion.1 = f32[8] fusion(), calls=dot"),
        ("custom-call.3", 850, 100, "%custom-call.3 = custom-call()"),
        ("fusion.4", 1200, 50, "%fusion.4 = f32[8] fusion()")])
    other = _plane(3, "/device:TPU:0 SparseCore", "XLA Ops",
                   [("fusion.9", 300, 500)])
    pd = ProfileData.from_text_proto(host + dev + other)
    return trace_reduce.reduce_profile(pd, spans={"run", "gen", "flush"})


def test_window_and_busy_time():
    r = _trace()
    assert r.window_s == pytest.approx(1000e-9)
    # ops clipped to the window: [100, 280] and [700, 950] -> 430 ns busy
    assert r.busy_s == pytest.approx(430e-9)
    assert r.n_ops == 4                    # fusion.4 lies after the window
    assert r.chips == 1


def test_idle_gaps_are_labelled_by_the_host_span():
    r = _trace()
    # gaps: [280, 700] is covered by run (280-500), gen (500-600), run
    # (600-700): run wins with 320 of 420 ns; [950, 1100] is in run until
    # 1050 (100 of 150 ns)
    assert dict(r.idle_by_span()) == pytest.approx({"run": 570e-9})
    assert r.gaps(0) == [(280.0, 700.0), (950.0, 1100.0)]
    assert r.label(500, 600) == "gen"
    assert r.label(1100, 1200) == "other"


def test_op_totals_and_selection():
    r = _trace()
    # fusion.1's first run is clipped to the window: 100 + 200 ns
    assert r.ops_by_name()[0] == ("fusion.1", pytest.approx(300e-9))
    assert r.op_seconds(lambda o: "dot" in o.hlo) == pytest.approx(300e-9)
    assert r.op_count(lambda o: o.name.startswith("copy")) == 1
    b = r.breakdown()
    assert [n for n, _ in b["device_ops"]] == ["fusion.1", "copy-start.2",
                                               "custom-call.3"]
    assert b["idle_gaps"] == [["run", pytest.approx(570e-9)]]


# how a TPU trace names its ops: by the whole HLO line
CONV_LINE = ('%closed_call.89 = f32[64,256]{1,0:T(8,128)} custom-call('
             'f32[64,128]{1,0:T(8,128)S(1)} %slice.180, f32[128,256]{1,0:T(8,'
             '128)S(1)} %slice.182), custom_call_target=\\"tpu_custom_call\\", '
             'operand_layout_constraints={f32[64,128]{1,0}, f32[128,256]{1,0}}')
HOP_LINE = ('%copy-done.7 = s8[64,8]{1,0:T(8,128)(4,1)} copy-done((s8[64,8]'
            '{1,0:T(8,128)(4,1)}, s8[64,8]{1,0:T(8,128)(4,1)S(5)}, u32[]{:S(2)})'
            ' %copy-start.7)')


WHILE_LINE = ('%while.2 = (s32[], f32[8,64]{1,0}) while((s32[], f32[8,64]'
              '{1,0}) %tuple.1), condition=%cond, body=%body')


def _tpu_trace(instrs=None) -> trace_reduce.Reduction:
    host = _plane(1, "/host:CPU", "python", [("traced", 0, 1000)])
    # three calls of the step; the trace was cut off 50 ns into the third
    dev = _plane(2, "/device:TPU:0", "XLA Ops", [
        (WHILE_LINE, 100, 360), (CONV_LINE, 100, 300), (HOP_LINE, 410, 50),
        (WHILE_LINE, 500, 360), (CONV_LINE, 500, 300), (WHILE_LINE, 900, 50)],
        more={"XLA Modules": [("jit_step(1)", 100, 360),
                              ("jit_step(1)", 500, 360),
                              ("jit_make(2)", 470, 20),
                              ("jit_step(1)", 900, 50)]})
    pd = ProfileData.from_text_proto(host + dev)
    return trace_reduce.reduce_profile(pd, instrs)


@pytest.mark.parametrize("indexed", [False, True])
def test_ops_named_by_their_whole_hlo_line(indexed):
    """The op's instruction is found by the name its line gives, or read
    from the line itself, and the breakdown names it by the short line."""
    line = CONV_LINE.replace('\\"', '"')
    instrs = ops.index(line) if indexed else None
    r = _tpu_trace(instrs)
    convs = [o for o in r.ops[0] if ops.is_conv(o)]
    assert [o.instr is not None for o in convs] == [indexed, indexed]
    assert r.op_seconds(ops.is_conv) == pytest.approx(600e-9)
    assert r.op_seconds(ops.is_hop) == pytest.approx(50e-9)
    assert r.breakdown()["device_ops"][0] == [
        "%closed_call.89 = f32[64,256] custom-call(f32[64,128], f32[128,256])",
        pytest.approx(600e-9)]


def test_containers_are_busy_time_and_no_op_of_their_own():
    r = _tpu_trace()
    # the whiles cover 100..460, 500..860 and 900..950 of the window
    assert r.busy_s == pytest.approx(770e-9)
    assert r.n_ops == 3 and r.op_count() == 3
    assert not any("while" in n for n, _ in r.breakdown()["device_ops"])


def test_step_calls_count_the_share_of_each_call_in_the_window():
    # two calls whole, the third by its 50 ns of the median 360 ns;
    # jit_make is not the step
    assert _tpu_trace().step_calls() == pytest.approx(2 + 50 / 360)


def test_a_call_partly_outside_the_window_counts_by_its_share():
    host = _plane(1, "/host:CPU", "python", [("traced", 0, 1000)])
    dev = _plane(2, "/device:TPU:0", "XLA Ops", [(CONV_LINE, 0, 10)],
                 more={"XLA Modules": [("jit_step(1)", -200, 400),
                                       ("jit_step(1)", 200, 400),
                                       ("jit_step(1)", 600, 600)]})
    r = trace_reduce.reduce_profile(ProfileData.from_text_proto(host + dev))
    assert r.step_calls() == pytest.approx(0.5 + 1 + 400 / 600)


def test_a_trace_without_the_window_span_is_refused():
    pd = ProfileData.from_text_proto(_plane(
        2, "/device:TPU:0", "XLA Ops", [("fusion.1", 0, 10)]))
    with pytest.raises(ValueError, match="traced"):
        trace_reduce.reduce_profile(pd)


def test_a_recorded_v5e_trace():
    """``unet368.stream`` traced on a TPU v5e with 8 calls in flight
    (``data/unet368.stream.v5e.*``: the trace and the step's HLO): every op
    is an instruction of the step; per call, 8 ticks of 14 convs, 3
    standalone dequants and 24 host copies; the device is busy throughout."""
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        (DATA / "unet368.stream.v5e.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((DATA / "unet368.stream.v5e.hlo.txt.gz")
                          .read_bytes()).decode()
    r = trace_reduce.reduce_profile(pd, ops.index(hlo),
                                    spans={"dispatch", "wait", "gen"})
    assert r.op_count(lambda o: o.instr is None) == 0
    calls = r.step_calls()
    assert calls == pytest.approx(r.window_s / 157.27e-3, rel=1e-3)
    for pred, per_tick in [(ops.is_conv, 14), (ops.is_bfp8, 3),
                           (ops.is_hop, 24)]:
        assert r.op_count(pred) / calls == pytest.approx(8 * per_tick,
                                                         rel=0.01)
    assert r.busy_s / r.window_s > 0.99
    top = [n for n, _ in r.breakdown()["device_ops"][:2]]
    assert all(n.startswith("%copy-done.") for n in top)
