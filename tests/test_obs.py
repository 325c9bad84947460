"""Observability layer tests (ISSUE 6 tentpole).

The contract under test:

* :class:`TraceRecorder` primitives are deterministic under an injected
  clock, and export valid Chrome trace-event JSON
  (``validate_chrome_trace`` is the same gate the CI smoke uses);
* the golden trace of a 2-stage UNet pipelined run: span ordering is
  fill -> steady -> drain, stage spans nest inside (share) their tick's
  interval, timestamps are monotone, and the span census matches the
  1F1B diagram exactly;
* **no-op parity** — running traced (null or live recorder) is
  bit-exact against the fused ``lax.scan`` path and leaves the lowered
  report untouched (zero report drift);
* spill-byte conservation is *emitted*: per edge,
  ``bytes_evicted == bytes_restored`` in the recorder totals;
* the façade round-trips :class:`ObsConfig` through
  ``Compiled.save``/``load`` and surfaces the :class:`ModelCheck` in
  ``Compiled.report()``;
* the serving front-end's per-request :class:`LatencyHistogram` counts
  every delivered frame.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import CompileSpec, Compiled
from repro.core import DSEConfig, build_unet_exec
from repro.core.graph import Graph
from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan
from repro.core.resources import Device
from repro.obs import (LatencyHistogram, NULL_RECORDER, NullRecorder,
                       ObsConfig, TraceRecorder, validate_chrome_trace)
from repro.runtime.streamer import lower_plan_pipelined

TINY = Device("tiny_obs", compute_units=4096, onchip_bits=300_000,
              offchip_gbps=64.0, freq_mhz=500.0, reconfig_s=0.0)
DSE_CFG = DSEConfig(batch=1, codecs=("none", "bfp8"), word_bits=16,
                    cut_kinds=("pool", "conv"))


def _stub_clock(step=1.0, start=0.0):
    """A deterministic counting clock: each call advances by ``step``."""
    state = [start]

    def clock():
        state[0] += step
        return state[0]

    return clock


def _two_stage_plan(g, evict_codec="bfp8", depth_thresh=4096.0):
    """Hand-built 2-stage plan over ``g`` (same recipe as test_streamer):
    the topological order cut in half, deep skip edges evicted."""
    g.compute_buffer_depths()
    topo = g.topo()
    stage = {n: min(i * 2 // len(topo), 1) for i, n in enumerate(topo)}
    layers = {v.name: LayerPlan(name=v.name, stage=stage[v.name])
              for v in g.vertices()}
    streams = []
    for e in g.edges():
        evict = evict_codec is not None and e.buffer_depth > depth_thresh
        streams.append(StreamPlan(e.src, e.dst, evicted=evict,
                                  codec=evict_codec if evict else "none"))
    return ExecutionPlan(model=g.name, device="tiny", n_stages=2,
                         layers=layers, streams=streams, topo_order=topo)


def _two_stage_executor(B=4):
    g = build_unet_exec()
    sx = lower_plan_pipelined(g, _two_stage_plan(g), microbatches=B,
                              kernel_mode="reference")
    xs = jax.random.normal(jax.random.PRNGKey(0), (B, 64, 32), jnp.float32)
    return sx, xs


# =============================================================================
# Recorder primitives under a stub clock
# =============================================================================

class TestTraceRecorder:
    def test_now_is_recorder_relative(self):
        rec = TraceRecorder(clock=_stub_clock())    # __init__ consumes t=1
        assert rec.now() == 1.0
        assert rec.now() == 2.0

    def test_span_context_measures_and_mutates_args(self):
        rec = TraceRecorder(clock=_stub_clock())
        with rec.span("work", track="t", cat="c", args={"a": 1}) as sa:
            sa["fps"] = 2.5                         # attach a result mid-span
        (s,) = rec.spans(track="t")
        assert s["name"] == "work" and s["cat"] == "c"
        assert s["args"] == {"a": 1, "fps": 2.5}
        assert s["ts"] == 1.0 and s["dur"] == 1.0   # two clock reads apart

    def test_add_span_clamps_negative_duration(self):
        rec = TraceRecorder(clock=_stub_clock())
        rec.add_span("x", 5.0, -1.0)
        assert rec.spans()[0]["dur"] == 0.0

    def test_counter_sets_incr_accumulates(self):
        rec = TraceRecorder(clock=_stub_clock())
        rec.counter("spill:a->b:bytes_evicted", 10, ts=0.0)
        rec.incr("spill:a->b:bytes_evicted", 5, ts=1.0)
        rec.incr("spill:a->b:bytes_evicted", ts=2.0)      # default delta 1
        assert rec.totals == {"spill:a->b:bytes_evicted": 16}
        # the emitted counter arg is keyed by the series' last segment
        ev = [e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "C"]
        assert ev[-1]["args"] == {"bytes_evicted": 16}

    def test_tracks_become_threads_in_first_use_order(self):
        rec = TraceRecorder(clock=_stub_clock())
        rec.add_span("a", 0.0, 1.0, track="pipeline")
        rec.add_span("b", 0.0, 1.0, track="stage0")
        rec.add_span("c", 0.0, 1.0, track="pipeline")
        assert rec.track_name(0) == "pipeline"
        assert rec.track_name(1) == "stage0"
        with pytest.raises(KeyError):
            rec.track_name(7)
        assert len(rec.spans(track="pipeline")) == 2

    def test_chrome_export_metadata_and_microseconds(self):
        rec = TraceRecorder(clock=_stub_clock())
        rec.add_span("tick", 1.0, 0.5, track="pipeline", cat="steady")
        rec.instant("stall", ts=2.0, track="queues")
        rec.counter("q:occupancy", 3, ts=2.0)
        data = rec.chrome_trace()
        assert data["displayTimeUnit"] == "ms"
        evs = data["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert {"name": "repro.obs"} in [e["args"] for e in meta
                                         if e["name"] == "process_name"]
        thread_names = {e["tid"]: e["args"]["name"] for e in meta
                        if e["name"] == "thread_name"}
        assert thread_names == {0: "pipeline", 1: "queues", 2: "counters"}
        (span,) = [e for e in evs if e["ph"] == "X"]
        assert span["ts"] == 1.0e6 and span["dur"] == 0.5e6  # seconds -> us
        (inst,) = [e for e in evs if e["ph"] == "i"]
        assert inst["s"] == "t"
        stats = validate_chrome_trace(data)
        assert stats["spans"] == 1 and stats["instants"] == 1
        assert stats["counters"] == 1

    def test_save_writes_loadable_valid_json(self, tmp_path):
        rec = TraceRecorder(clock=_stub_clock())
        with rec.span("frame"):
            pass
        p = rec.save(tmp_path / "trace.json")
        stats = validate_chrome_trace(json.loads(p.read_text()))
        assert stats["spans"] == 1


class TestNullRecorder:
    def test_no_op_contract(self):
        rec = NullRecorder()
        assert rec.enabled is False and NULL_RECORDER.enabled is False
        assert rec.now() == 0.0
        with rec.span("x", args={"a": 1}) as sa:
            sa["ignored"] = True                    # mutable but discarded
        rec.add_span("x", 0.0, 1.0)
        rec.instant("x")
        rec.counter("c", 1.0)
        rec.incr("c")
        assert rec.totals == {}

    def test_trace_recorder_is_a_drop_in(self):
        # instrumented code holds a NullRecorder-typed slot; the live
        # recorder substitutes via subclassing, not duck-typing luck
        assert isinstance(TraceRecorder(clock=_stub_clock()), NullRecorder)


# =============================================================================
# Chrome trace schema validation (the CI smoke's gate)
# =============================================================================

class TestValidateChromeTrace:
    def _valid(self):
        return {"traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": "p"}},
            {"ph": "X", "name": "tick", "pid": 0, "tid": 1, "ts": 0.0,
             "dur": 1.0},
            {"ph": "i", "name": "stall", "pid": 0, "tid": 1, "ts": 2.0,
             "s": "t"},
            {"ph": "C", "name": "occ", "pid": 0, "tid": 2, "ts": 2.0,
             "args": {"occ": 3}},
        ]}

    def test_valid_trace_stats(self):
        stats = validate_chrome_trace(self._valid())
        assert stats == {"events": 4, "spans": 1, "instants": 1,
                         "counters": 1, "metadata": 1, "tracks": 3}

    @pytest.mark.parametrize("mutate,msg", [
        (lambda d: "not a dict", "traceEvents"),
        (lambda d: {"traceEvents": []}, "non-empty"),
        (lambda d: d["traceEvents"].__setitem__(1, "ev") or d,
         "not an object"),
        (lambda d: d["traceEvents"][1].update(ph="Z") or d, "unknown phase"),
        (lambda d: d["traceEvents"][1].update(name="") or d, "name"),
        (lambda d: d["traceEvents"][1].update(tid="one") or d, "integers"),
        (lambda d: d["traceEvents"][1].update(ts=-1.0) or d, "non-negative"),
        (lambda d: d["traceEvents"][1].__delitem__("dur") or d, "dur"),
        (lambda d: d["traceEvents"][3].update(args={"occ": "3"}) or d,
         "numbers"),
    ])
    def test_malformed_traces_rejected(self, mutate, msg):
        with pytest.raises(ValueError, match=msg):
            validate_chrome_trace(mutate(self._valid()))


# =============================================================================
# Golden trace: 2-stage UNet, B=4 -> T=5 (fill 1, steady 3, drain 1)
# =============================================================================

class TestGoldenTrace:
    def _traced(self):
        sx, xs = _two_stage_executor(B=4)
        rec = TraceRecorder(clock=_stub_clock())
        ys, mc = sx.run_traced(xs, rec, measure_stages=False)
        return sx, xs, rec, ys, mc

    def test_span_ordering_fill_steady_drain(self):
        _, _, rec, _, mc = self._traced()
        ticks = [s for s in rec.spans(track="pipeline") if s["name"] == "tick"]
        assert [s["cat"] for s in ticks] == \
            ["fill", "steady", "steady", "steady", "drain"]
        assert [s["args"]["tick"] for s in ticks] == [0, 1, 2, 3, 4]
        assert mc.ticks_measured == 5 and mc.steady_measured == 3
        assert mc.ok

    def test_timestamps_monotonic(self):
        _, _, rec, _, _ = self._traced()
        ticks = rec.spans(track="pipeline")
        ts = [s["ts"] for s in ticks]
        assert ts == sorted(ts) and len(set(ts)) == len(ts)  # strict
        for s in ticks:
            assert s["dur"] >= 0.0

    def test_stage_spans_nest_inside_their_tick(self):
        """Stage spans share their tick's exact interval — the overlap of
        stage0/stage1 lanes within a tick *is* the pipeline diagram."""
        _, _, rec, _, _ = self._traced()
        interval = {s["args"]["tick"]: (s["ts"], s["dur"])
                    for s in rec.spans(track="pipeline")}
        census = []
        for j in (0, 1):
            stage = rec.spans(track=f"stage{j}")
            assert [s["name"] for s in stage] == [f"mb{b}" for b in range(4)]
            for s in stage:
                t = s["args"]["tick"]
                assert (s["ts"], s["dur"]) == interval[t]
                assert s["args"]["stage"] == j
                census.append((t, j))
        # the 1F1B census: stage j runs microbatch b at tick t = b + j
        assert sorted(census) == sorted(
            (b + j, j) for j in (0, 1) for b in range(4))

    def test_golden_span_census_and_valid_export(self, tmp_path):
        _, _, rec, _, _ = self._traced()
        stats = validate_chrome_trace(
            json.loads(rec.save(tmp_path / "t.json").read_text()))
        # 5 tick spans + 2 stages x 4 microbatch spans, nothing else
        assert stats["spans"] == 5 + 8
        assert stats["instants"] == 0      # well-sized queues: no stalls
        # every crossing edge's ring emitted occupancy counters
        occ = [k for k in rec.totals if k.endswith(":occupancy")]
        assert occ and all(rec.totals[k] == 0 for k in occ)  # drained

    def test_spill_bytes_conserved_per_edge(self):
        sx, _, rec, _, _ = self._traced()
        assert sx.report.spills            # the plan does spill
        evicted = {k.split(":")[1]: v for k, v in rec.totals.items()
                   if k.startswith("spill:") and k.endswith(":bytes_evicted")}
        assert evicted
        for edge, n in evicted.items():
            assert n > 0
            assert rec.totals[f"spill:{edge}:bytes_restored"] == n
        for k, v in rec.totals.items():
            if k.startswith("bfp8:") and k.endswith(":encodes"):
                assert rec.totals[k.replace(":encodes", ":decodes")] == v


# =============================================================================
# No-op parity: tracing must not change a single bit
# =============================================================================

class TestNoOpParity:
    def test_traced_outputs_bit_exact_and_zero_report_drift(self):
        sx, xs = _two_stage_executor(B=4)
        before = sx.report.summary()
        y_fused = np.asarray(sx(xs))
        y_null, mc_null = sx.run_traced(xs, measure_stages=False)
        y_live, mc_live = sx.run_traced(xs, TraceRecorder(),
                                        measure_stages=False)
        np.testing.assert_array_equal(np.asarray(y_null), y_fused)
        np.testing.assert_array_equal(np.asarray(y_live), y_fused)
        # zero report drift: tracing leaves the lowered report untouched,
        # and the ModelCheck itself is recorder-independent
        assert sx.report.summary() == before
        assert mc_null.summary() == mc_live.summary()
        assert mc_null.ok and mc_live.ok


# =============================================================================
# Façade: ObsConfig round-trip, trace(), report()
# =============================================================================

def _spec(**kw):
    kw.setdefault("device", TINY)
    kw.setdefault("strategy", "dse")
    kw.setdefault("dse", DSE_CFG)
    kw.setdefault("kernel_mode", "reference")
    return CompileSpec(model="unet_exec", **kw)


class TestFacadeObs:
    def test_obsconfig_dict_roundtrip_ignores_unknown_keys(self):
        cfg = ObsConfig(enabled=True, trace_path="t.json")
        d = cfg.to_dict()
        assert d == {"enabled": True, "trace_path": "t.json", "slo": None,
                     "flight_capacity": 0, "flight_path": None}
        assert ObsConfig.from_dict(d) == cfg
        assert ObsConfig.from_dict(d | {"future_knob": 1}) == cfg
        assert ObsConfig.from_dict({}) == ObsConfig()

    def test_obsconfig_roundtrips_nested_slo_config(self):
        from repro.obs import SloConfig
        cfg = ObsConfig(slo=SloConfig(window=8, p99_target_s=0.5),
                        flight_capacity=256, flight_path="f.json")
        d = cfg.to_dict()
        assert d["slo"]["window"] == 8          # nests as a plain dict
        back = ObsConfig.from_dict(json.loads(json.dumps(d)))
        assert back == cfg and isinstance(back.slo, SloConfig)

    def test_save_load_roundtrips_obs_config(self, tmp_path):
        c = repro.compile(_spec(mode="staged",
                                obs=ObsConfig(enabled=True,
                                              trace_path="t.json")))
        p = c.save(tmp_path / "design.smof.json")
        c2 = Compiled.load(p)
        assert c2.spec.obs == ObsConfig(enabled=True, trace_path="t.json")
        # and a pre-obs artifact (no "obs" key) loads with the default
        d = json.loads(p.read_text())
        d.pop("obs")
        (tmp_path / "old.smof.json").write_text(json.dumps(d))
        assert Compiled.load(tmp_path / "old.smof.json").spec.obs \
            == ObsConfig()

    def test_pipelined_trace_writes_valid_trace_and_reports_modelcheck(
            self, tmp_path):
        c = repro.compile(_spec(mode="pipelined", microbatches=4))
        assert "model_check" not in c.report()      # not traced yet
        path = tmp_path / "run.json"
        y, mc = c.trace(path=path)
        assert mc is not None and mc.ticks_measured == mc.ticks_predicted
        validate_chrome_trace(json.loads(path.read_text()))
        rep = c.report()
        assert rep["model_check"]["ok"] == mc.ok
        assert rep["model_check"]["ticks"]["measured"] == mc.ticks_measured
        err = rep["model_check"]["max_stage_rel_err"]
        if c.plan.n_stages > 1:                     # measured-vs-fitted
            assert err is not None and err >= 0.0   # residuals per stage

    def test_staged_trace_records_frame_span_without_modelcheck(self):
        c = repro.compile(_spec(mode="staged"))
        rec = TraceRecorder(clock=_stub_clock())
        x = jax.random.normal(jax.random.PRNGKey(0), c.input_shape(),
                              jnp.float32)
        y, mc = c.trace(x, recorder=rec)
        assert mc is None
        np.testing.assert_array_equal(np.asarray(y), np.asarray(c.run(x)))
        (frame,) = rec.spans(track="host")
        assert frame["name"] == "frame"
        # sequential spill accounting: one round-trip per spilled edge
        for k, v in rec.totals.items():
            if k.startswith("spill:") and k.endswith(":bytes_evicted"):
                assert rec.totals[k.replace("_evicted", "_restored")] == v


# =============================================================================
# LatencyHistogram + the serving front-end integration
# =============================================================================

class TestLatencyHistogram:
    def test_empty_summary_is_zeroed(self):
        s = LatencyHistogram().summary()
        assert s == {"count": 0, "mean_s": 0.0, "p50_s": 0.0, "p95_s": 0.0,
                     "p99_s": 0.0, "min_s": 0.0, "max_s": 0.0}

    def test_records_and_conservative_quantiles(self):
        h = LatencyHistogram()
        for v in (1e-6, 1e-6, 1e-6, 1.0):
            h.record(v)
        s = h.summary()
        assert s["count"] == 4 and s["max_s"] == 1.0
        assert s["mean_s"] == pytest.approx((3e-6 + 1.0) / 4)
        assert s["p50_s"] == 1e-6                   # exact bucket edge
        assert 1.0 <= s["p95_s"] <= 2.0             # upper-edge conservative

    def test_overflow_bucket_reports_max(self):
        h = LatencyHistogram(base=1e-6, n_buckets=4)   # top edge: 8 us
        h.record(1.0)
        assert h.quantile(1.0) == 1.0               # overflow -> max_s
        assert h.counts[-1] == 1

    def test_stream_server_histogram_counts_every_frame(self):
        from repro.serving.engine import GraphStreamServer
        g = build_unet_exec(positions=32, levels=2)
        g.compute_buffer_depths()
        topo = g.topo()
        layers = {n: LayerPlan(name=n, stage=0) for n in topo}
        plan = ExecutionPlan(model=g.name, device="tiny", n_stages=1,
                             layers=layers,
                             streams=[StreamPlan(e.src, e.dst)
                                      for e in g.edges()],
                             topo_order=topo)
        srv = GraphStreamServer(g, plan, microbatches=2,
                                kernel_mode="reference")
        assert srv.latency.summary()["count"] == 0
        tickets = [srv.submit(np.zeros((32, 32), np.float32))
                   for _ in range(3)]               # 1.5 streams -> padding
        srv.flush()
        s = srv.latency.summary()
        assert s["count"] == len(tickets) == 3
        assert s["max_s"] > 0.0 and s["p95_s"] >= s["p50_s"] > 0.0


# =============================================================================
# smof.* host spans on the profiler's clock
# =============================================================================

def _profiled(fn):
    """Run ``fn()`` under a CPU ``jax.profiler`` trace; returns the
    ``smof.*`` events of the host plane as ``(name, start_ns, end_ns)``,
    in start order."""
    import glob
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.end_ns)
                   for p in pd.planes if p.name == "/host:CPU"
                   for line in p.lines for e in line.events
                   if e.name.startswith("smof.")), key=lambda e: e[1])


def _in_turn(events, names):
    """The events of ``names``, each after the last one ended."""
    got = [e for e in events if e[0] in names]
    assert [e[0] for e in got] == list(names), got
    for (_, _, end), (_, start, _) in zip(got, got[1:]):
        assert start >= end
    return got


class TestProfilerSpans:
    FLUSH = tuple(f"smof.flush.{p}"
                  for p in ("stack", "h2d", "run", "d2h", "claim"))

    def test_compile_and_run_spans(self):
        box = {}

        def work():
            box["c"] = repro.compile(_spec(mode="pipelined", microbatches=2))
            c = box["c"]
            xs = jnp.zeros((2,) + c.input_shape(), jnp.float32)
            jax.block_until_ready(c.run(xs))
            jax.block_until_ready(c.run(xs))
        events = _profiled(work)
        _in_turn(events, ("smof.compile.search", "smof.compile.lower"))
        runs = [e for e in events if e[0] == "smof.run"]
        assert len(runs) == 2 and runs[1][1] >= runs[0][2]

    def test_flush_spans_in_order_per_chunk(self):
        c = repro.compile(_spec(mode="pipelined", microbatches=2))
        srv = c.serve()
        for _ in range(3):                  # two chunks, the second padded
            srv.submit(np.zeros(c.input_shape(), np.float32))
        events = _profiled(srv.flush)
        flush = [e for e in events if e[0].startswith("smof.flush.")]
        assert len(flush) == 2 * len(self.FLUSH)
        _in_turn(flush[:5], self.FLUSH)
        _in_turn(flush[5:], self.FLUSH)
        assert flush[5][1] >= flush[4][2]

    def test_recorder_spans_land_in_the_profile(self):
        rec = TraceRecorder()

        def spans():
            with rec.span("frame"):
                with rec.span("tick"):
                    pass
            with NULL_RECORDER.span("ignored"):
                pass
        events = _profiled(spans)
        (frame,) = [e for e in events if e[0] == "smof.frame"]
        (tick,) = [e for e in events if e[0] == "smof.tick"]
        assert frame[1] <= tick[1] and tick[2] <= frame[2]
        assert not [e for e in events if e[0] == "smof.ignored"]
        assert [s["name"] for s in rec.spans()] == ["frame", "tick"]


# =============================================================================
# smof.* device scopes
# =============================================================================

class TestDeviceScopes:
    def test_a_slash_in_a_vertex_name_reads_as_a_bar(self):
        """A vertex name is metadata: a graph whose names hold ``/`` (as a
        reloaded artifact may) compiles and runs bitwise as the same graph
        with plain names, and each scope stays one ``op_name`` component."""
        g = build_unet_exec()
        d = g.to_json_dict()
        new = {v["name"]: f"enc/{v['name']}" for v in d["vertices"]}
        for v in d["vertices"]:
            v["name"] = new[v["name"]]
        for e in d["edges"]:
            e["src"], e["dst"] = new[e["src"]], new[e["dst"]]
        gs = Graph.from_json_dict(d)
        B = 2
        xs = jax.random.normal(jax.random.PRNGKey(0), (B, 64, 32),
                               jnp.float32)
        sx = lower_plan_pipelined(g, _two_stage_plan(g), microbatches=B,
                                  kernel_mode="reference")
        sxs = lower_plan_pipelined(gs, _two_stage_plan(gs), microbatches=B,
                                   kernel_mode="reference")
        params = {new[k]: w for k, w in sx.params.items()}
        np.testing.assert_array_equal(np.asarray(sxs.fn(params, xs)),
                                      np.asarray(sx(xs)))
        text = sxs.fn.lower(params, xs).as_text(debug_info=True)
        assert "/smof.conv:enc|conv_" in text
        assert "smof.conv:enc/" not in text
