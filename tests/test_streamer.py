"""Pipelined streaming executor tests.

The acceptance contract (ISSUE 2 / docs/ARCHITECTURE.md):
* per microbatch, the pipelined executor is numerically equivalent to the
  sequential ``lower_plan`` pipeline on the same plan — BFP8 codec error
  included identically in both (the same codec functions run in the same
  pad->quantise->dequantise->slice composition, only *when* changes);
* ``StreamReport`` spill bit-volumes are bit-exact against ``SpillReport``
  for the same plan;
* on a >=3-stage UNet exec graph with >=8 microbatches, measured
  steady-state throughput beats the sequential executor and lands closer
  to the Eq. 6 ``1/max_j(L_j)`` pipeline estimate than to the Eq. 5
  sequential sum (latencies measured per stage, same dispatch regime the
  sequential schedule pays).
"""
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DSEConfig, build_unet_exec, build_yolo_head_exec,
                        plan_from_dse, run_dse)
from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan
from repro.core.resources import Device
from repro.runtime.executor import lower_plan
from repro.runtime.streamer import (RingBuffer, StreamReport,
                                    build_queues, build_schedule,
                                    eq5_sequential_time, eq6_pipeline_time,
                                    lower_plan_pipelined,
                                    measured_stage_latencies, queue_specs,
                                    simulate_schedule, stage_latencies)

TINY = Device("tiny", compute_units=4096, onchip_bits=300_000,
              offchip_gbps=64.0, freq_mhz=500.0, reconfig_s=0.0)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _staged_plan(g, n_stages=3, evict_codec=None, depth_thresh=4096.0):
    """Hand-built plan: stages cut the topological order into equal thirds;
    optionally evict every deep (skip) edge with ``evict_codec``."""
    g.compute_buffer_depths()
    topo = g.topo()
    stage = {n: min(i * n_stages // len(topo), n_stages - 1)
             for i, n in enumerate(topo)}
    layers = {v.name: LayerPlan(name=v.name, stage=stage[v.name])
              for v in g.vertices()}
    streams = []
    for e in g.edges():
        evict = evict_codec is not None and e.buffer_depth > depth_thresh
        streams.append(StreamPlan(e.src, e.dst, evicted=evict,
                                  codec=evict_codec if evict else "none"))
    return ExecutionPlan(model=g.name, device="tiny", n_stages=n_stages,
                         layers=layers, streams=streams, topo_order=topo)


def _dse_plan(g, codecs=("none",), cut_kinds=("pool", "conv")):
    res = run_dse(g, TINY, DSEConfig(batch=1, codecs=codecs, word_bits=16,
                                     cut_kinds=cut_kinds))
    return plan_from_dse(g.name, TINY.name, res)


def _sequential_outputs(low, xs):
    return np.stack([np.asarray(low(xs[b])) for b in range(xs.shape[0])])


# =============================================================================
# Schedule
# =============================================================================

class TestSchedule:
    def test_shape_of_the_1f1b_diagram(self):
        s = build_schedule(3, 8)
        assert s.ticks == 10
        assert len(s.tasks()) == 3 * 8            # every (stage, mb) once
        assert s.active_stages(0) == [0]          # fill: only stage 0
        assert s.active_stages(2) == [0, 1, 2]    # steady: all stages
        assert s.active_stages(9) == [2]          # drain: only the tail
        assert [s.phase(t) for t in (0, 1, 2, 7, 8, 9)] == \
            ["fill", "fill", "steady", "steady", "drain", "drain"]

    def test_occupancy_and_stalls(self):
        s = build_schedule(4, 8)
        for j in range(4):
            assert s.stage_active_ticks(j) == 8
            assert s.stage_idle_ticks(j) == 3      # S-1 bubbles
            assert s.stage_occupancy(j) == 8 / 11

    def test_degenerate_single_stage(self):
        s = build_schedule(1, 5)
        assert s.ticks == 5 and s.phase(0) == "steady"

    def test_invalid_schedule_rejected(self):
        with pytest.raises(ValueError):
            build_schedule(0, 4)

    def test_eq5_eq6_estimators(self):
        lat = [3.0, 7.0, 2.0]
        assert eq5_sequential_time(lat) == 12.0
        assert eq6_pipeline_time(lat) == 7.0

    def test_stage_latencies_analytic_hook(self):
        g = build_unet_exec()
        plan = _staged_plan(g)
        lat = stage_latencies(g, plan)
        assert len(lat) == 3 and all(l > 0 for l in lat)
        hooked = stage_latencies(g, plan, hook=lambda j, sg: float(j + 1))
        assert hooked == [1.0, 2.0, 3.0]


# =============================================================================
# Queues
# =============================================================================

class TestQueues:
    def test_ring_buffer_stall_accounting(self):
        q = RingBuffer(2)
        assert q.pop() is None and q.pop_stalls == 1
        assert q.push("a") and q.push("b")
        assert not q.push("c") and q.push_stalls == 1   # over capacity
        # high_water saturates at capacity: the modelled ring never
        # physically holds more than `capacity` entries, the overflowing
        # push is accounted as a stall instead
        assert q.high_water == 2
        assert q.pop() == "a"

    def test_ring_buffer_push_full_pop_empty_counters(self):
        """Direct unit contract for the stall counters (ISSUE 6 fix):
        every push against a full ring counts exactly one push stall,
        every pop from an empty ring exactly one pop stall, and neither
        corrupts FIFO order or the saturated high-water mark."""
        q = RingBuffer(3)
        # pop-empty: N pops on an empty ring -> N pop stalls, nothing else
        for k in range(1, 4):
            assert q.pop() is None
            assert q.pop_stalls == k
        assert q.push_stalls == 0 and q.high_water == 0 and len(q) == 0

        # fill exactly to capacity: no stalls, high_water rides occupancy
        for i in range(3):
            assert q.push(i)
            assert q.high_water == i + 1
        assert q.push_stalls == 0

        # push-full: each overflowing push counts one stall; high_water
        # stays pinned at capacity (no off-by-one above the ring's size)
        for k in range(1, 3):
            assert not q.push(100 + k)
            assert q.push_stalls == k
            assert q.high_water == q.capacity == 3
        # FIFO order survives the overflow accounting
        assert [q.pop() for _ in range(5)] == [0, 1, 2, 101, 102]
        assert q.pop() is None and q.pop_stalls == 4

    def test_ring_buffer_emits_occupancy_and_stall_events(self):
        from repro.obs import TraceRecorder
        rec = TraceRecorder(clock=None)
        q = RingBuffer(2, name="a->b", recorder=rec)
        q.push("x", ts=0.0)
        q.push("y", ts=1.0)
        q.push("z", ts=2.0)          # overflow -> stall instant
        q.pop(ts=3.0)
        assert rec.totals["queue:a->b:occupancy"] == 2  # saturated, not 3
        names = [ev["name"] for ev in rec.chrome_trace()["traceEvents"]]
        assert "queue:a->b:push_stall" in names

    def test_specs_cover_crossing_edges_with_eq1_capacity(self):
        g = build_unet_exec()
        plan = _staged_plan(g)
        from repro.runtime.executor import analyze_plan
        an = analyze_plan(g, plan, use_pallas=False, interpret=True)
        specs = queue_specs(g, an.stage_of, an.out_shape)
        assert specs                                   # stages do cross
        for (u, w), s in specs.items():
            assert an.stage_of[w] > an.stage_of[u]
            assert s.delay == an.stage_of[w] - an.stage_of[u]
            # floored at the two DMA-burst FIFOs AND the executed
            # shift-register depth for the crossing
            assert s.capacity >= max(2, s.delay)
            assert s.capacity_words == 256.0           # Eq. 1 d_b'

    def test_simulation_high_water_tracks_stage_distance(self):
        g = build_unet_exec()
        plan = _staged_plan(g)
        from repro.runtime.executor import analyze_plan
        an = analyze_plan(g, plan, use_pallas=False, interpret=True)
        specs = queue_specs(g, an.stage_of, an.out_shape)
        queues = build_queues(specs)
        sim = simulate_schedule(
            build_schedule(3, 8), queues,
            producer_stage={e: an.stage_of[e[0]] for e in specs},
            consumer_stage={e: an.stage_of[e[1]] for e in specs})
        assert sim["ticks"] == 10
        for e, st in sim["queues"].items():
            assert st["high_water"] >= specs[e].delay
            assert st["occupancy"] == 0                # fully drained
            assert st["pop_stalls"] == 0


# =============================================================================
# Numerical equivalence with the sequential executor
# =============================================================================

class TestParity:
    def _check(self, g, plan, B=8, seed=0, in_shape=(64, 32)):
        low = lower_plan(g, plan, kernel_mode="reference")
        sx = lower_plan_pipelined(g, plan, microbatches=B,
                                  kernel_mode="reference")
        xs = jax.random.normal(jax.random.PRNGKey(seed), (B,) + in_shape,
                               jnp.float32)
        ys = np.asarray(sx(xs))
        want = _sequential_outputs(low, xs)
        np.testing.assert_allclose(ys, want, rtol=1e-5, atol=1e-6)
        return sx, low

    def test_dse_multistage_plan_unet(self):
        g = build_unet_exec()
        plan = _dse_plan(g)
        assert plan.n_stages >= 2
        self._check(g, plan)

    def test_dse_plan_with_bfp8_yolo_head(self):
        g = build_yolo_head_exec()
        plan = _dse_plan(g, codecs=("none", "bfp8"))
        self._check(g, plan, seed=1)

    def test_bfp8_skip_eviction_across_stages(self):
        """Cross-stage BFP8 spills carry *encoded* buffers through the
        pipeline and still reproduce the sequential codec error exactly."""
        g = build_unet_exec()
        plan = _staged_plan(g, evict_codec="bfp8")
        assert any(s.evicted for s in plan.streams)
        sx, low = self._check(g, plan, seed=2)
        # the codec really ran: pipelined output differs from the dense ref
        from repro.runtime.executor import reference_pipeline
        ref = reference_pipeline(g)
        x = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
        xs = jnp.broadcast_to(x, (8, 64, 32))
        rel = (np.abs(np.asarray(sx(xs))[0] - np.asarray(ref(x))).max()
               / np.abs(np.asarray(ref(x))).max())
        assert 0.0 < rel < 0.15

    def test_single_stage_plan_degenerates_to_batched_scan(self):
        g = build_unet_exec(positions=32, levels=2)
        plan = _staged_plan(g, n_stages=1)
        sx, _ = self._check(g, plan, B=4, in_shape=(32, 32))
        assert sx.n_stages == 1 and sx.report.ticks == 4

    def test_wrong_stream_shape_rejected(self):
        g = build_unet_exec()
        plan = _staged_plan(g)
        sx = lower_plan_pipelined(g, plan, microbatches=4,
                                  kernel_mode="reference")
        with pytest.raises(ValueError, match="stream shape"):
            sx(jnp.zeros((3, 64, 32), jnp.float32))

    def test_backward_stage_edge_rejected(self):
        g = build_unet_exec()
        plan = _staged_plan(g)
        # corrupt: force a later vertex into an earlier stage
        last = plan.topo_order[-1]
        plan.layers[last].stage = 0
        with pytest.raises(ValueError, match="backward|empty"):
            lower_plan_pipelined(g, plan, microbatches=4,
                                 kernel_mode="reference")


# =============================================================================
# StreamReport
# =============================================================================

class TestStreamReport:
    def test_spill_bit_volumes_bit_exact_vs_sequential(self):
        g = build_unet_exec()
        for plan in (_dse_plan(g, codecs=("none", "bfp8")),
                     _staged_plan(g, evict_codec="bfp8")):
            low = lower_plan(g, plan, kernel_mode="reference")
            sx = lower_plan_pipelined(g, plan, microbatches=8,
                                      kernel_mode="reference")
            assert isinstance(sx.report, StreamReport)
            assert sx.report.spills == low.report.spills
            assert (sx.report.total_offchip_bits
                    == low.report.total_offchip_bits)
            assert (sx.report.static_weight_bits
                    == low.report.static_weight_bits)

    def test_schedule_accounting_fields(self):
        g = build_unet_exec()
        plan = _staged_plan(g)
        sx = lower_plan_pipelined(g, plan, microbatches=8,
                                  kernel_mode="reference")
        r = sx.report
        assert r.n_stages == 3 and r.microbatches == 8 and r.ticks == 10
        assert r.stage_occupancy == [8 / 10] * 3
        assert r.stage_stalls == [2] * 3               # S-1 bubbles
        assert len(r.stage_latency) == 3
        assert r.eq5_time == sum(r.stage_latency)
        assert r.eq6_time == max(r.stage_latency)
        assert r.bottleneck_stage == r.stage_latency.index(max(r.stage_latency))
        s = r.summary()
        assert s["ticks"] == 10 and s["placement"] == "interleave"
        assert s["total_offchip_bits"] == r.total_offchip_bits


# =============================================================================
# ModelCheck: measured walk vs the Eq. 5/6 schedule and Eq. 1 queue sizing
# =============================================================================

class TestModelCheck:
    def test_steady_ticks_match_eq6_schedule_exactly(self):
        """The traced run's measured steady-state tick count equals the
        Eq. 6 schedule prediction B - S + 1 exactly (stub clock: the
        invariant is structural, not timing-dependent)."""
        from repro.obs import TraceRecorder
        g = build_unet_exec()
        plan = _staged_plan(g)
        sx = lower_plan_pipelined(g, plan, microbatches=8,
                                  kernel_mode="reference")
        ticking = [0.0]

        def stub_clock():
            ticking[0] += 1.0
            return ticking[0]

        rec = TraceRecorder(clock=stub_clock)
        xs = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32),
                               jnp.float32)
        ys, mc = sx.run_traced(xs, rec, measure_stages=False)
        assert ys.shape == (8, ys.shape[1])
        sched = sx.schedule
        assert mc.ticks_measured == mc.ticks_predicted == sched.ticks == 10
        assert (mc.steady_measured == mc.steady_predicted
                == sched.steady_ticks == 6)
        assert mc.ticks_ok and mc.queues_ok and mc.ok
        # and the emitted trace agrees: one steady tick span per steady tick
        steady = [s for s in rec.spans(track="pipeline")
                  if s["name"] == "tick" and s["cat"] == "steady"]
        assert len(steady) == sched.steady_ticks

    def test_deliberately_mis_sized_queue_is_flagged(self):
        """Shrinking one crossing's ring below its stage distance makes the
        schedule walk overflow it — ModelCheck must flag the design."""
        import dataclasses as dc
        from repro.obs import check_stream
        g = build_unet_exec()
        plan = _staged_plan(g)
        sx = lower_plan_pipelined(g, plan, microbatches=8,
                                  kernel_mode="reference")
        # correctly-sized queues (the lowering's own simulation) pass
        assert check_stream(sx.report).queues_ok

        specs = dict(sx._queue_specs)
        edge = max(specs, key=lambda e: specs[e].delay)
        assert specs[edge].delay >= 2
        specs[edge] = dc.replace(specs[edge], capacity=1)
        sim = simulate_schedule(
            sx.schedule, build_queues(specs),
            producer_stage={e: sx._stage_of[e[0]] for e in specs},
            consumer_stage={e: sx._stage_of[e[1]] for e in specs})
        mc = check_stream(sx.report, queue_stats={
            f"{u}->{w}": st for (u, w), st in sim["queues"].items()})
        assert not mc.queues_ok and not mc.ok
        bad = [q for q in mc.queues if not q.ok]
        assert bad and any(q.push_stalls > 0 for q in bad)
        assert f"{edge[0]}->{edge[1]}" in {q.edge for q in bad}


# =============================================================================
# Throughput: the Eq. 5 -> Eq. 6 move (ISSUE 2 acceptance)
# =============================================================================

class TestThroughput:
    def test_pipelined_beats_sequential_and_tracks_eq6(self):
        """>=3 stages, >=8 microbatches: executed steady-state throughput
        exceeds the sequential executor's and sits closer (log-space) to
        the Eq. 6 slowest-stage bound than to the Eq. 5 sum."""
        import time

        g = build_unet_exec()
        plan = _dse_plan(g)
        assert plan.n_stages >= 3
        B = 16
        low = lower_plan(g, plan, kernel_mode="reference")
        sx = lower_plan_pipelined(g, plan, microbatches=B,
                                  kernel_mode="reference")
        xs = jax.random.normal(jax.random.PRNGKey(0), (B, 64, 32),
                               jnp.float32)
        sx(xs).block_until_ready()                 # compile
        _sequential_outputs(low, xs)

        def frame_time(fn):
            t0 = time.perf_counter()
            fn()
            return (time.perf_counter() - t0) / B

        # best of 5 each, timed alternately, so a burst of load from
        # another process lands on both sides rather than on one
        t_pipe = t_seq = math.inf
        for _ in range(5):
            t_pipe = min(t_pipe,
                         frame_time(lambda: sx(xs).block_until_ready()))
            t_seq = min(t_seq, frame_time(lambda: jax.block_until_ready(
                [low(xs[b]) for b in range(B)])))
        lat = measured_stage_latencies(sx, xs[0])
        e5 = eq5_sequential_time(lat)
        e6 = eq6_pipeline_time(lat)
        assert e6 < e5                              # stages are not uniform
        assert t_pipe < t_seq, (t_pipe, t_seq)
        d6 = abs(math.log(t_pipe / e6))
        d5 = abs(math.log(t_pipe / e5))
        assert d6 < d5, (t_pipe, e6, e5)


# =============================================================================
# Plan determinism satellites
# =============================================================================

class TestPlanOrdering:
    def test_stage_layers_topological_not_insertion_order(self):
        g = build_unet_exec()
        topo = g.topo()
        # adversarial insertion order: reversed
        layers = {n: LayerPlan(name=n, stage=0) for n in reversed(topo)}
        plan = ExecutionPlan(model=g.name, device="t", n_stages=1,
                             layers=layers, streams=[], topo_order=topo)
        assert plan.stage_layers(0) == topo

    def test_plan_from_dse_layers_in_topo_order(self):
        g = build_unet_exec()
        plan = _dse_plan(g)
        assert plan.topo_order == g.topo()
        seen = []
        for j in range(plan.n_stages):
            seen += plan.stage_layers(j)
        assert seen == [n for n in g.topo()]       # stages tile the topo

    def test_from_json_ignores_unknown_keys(self):
        g = build_unet_exec(positions=32, levels=2)
        plan = _staged_plan(g, n_stages=2)
        import json
        d = json.loads(plan.to_json())
        d["a_future_field"] = {"x": 1}
        d["layers"][plan.topo_order[0]]["future_layer_knob"] = 3
        d["streams"][0]["future_stream_knob"] = True
        back = ExecutionPlan.from_json(json.dumps(d))
        assert back.n_stages == plan.n_stages
        assert back.stage_layers(0) == plan.stage_layers(0)
        assert back.streams[0].src == plan.streams[0].src

    def test_json_roundtrip_preserves_topo_order(self):
        g = build_unet_exec(positions=32, levels=2)
        plan = _staged_plan(g, n_stages=2)
        back = ExecutionPlan.from_json(plan.to_json())
        assert back.topo_order == plan.topo_order
        assert back.stage_layers(1) == plan.stage_layers(1)


# =============================================================================
# Multi-device stage placement (shard_map ring)
# =============================================================================

class TestShardMapPlacement:
    def test_ring_pipeline_matches_sequential(self):
        """One stage per (host-platform) device; ppermute-ring transit."""
        code = textwrap.dedent("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import build_unet_exec
            from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan
            from repro.runtime.executor import lower_plan
            from repro.runtime.streamer import lower_plan_pipelined
            g = build_unet_exec()
            g.compute_buffer_depths()
            topo = g.topo(); S = 3
            stage = {n: min(i * S // len(topo), S - 1)
                     for i, n in enumerate(topo)}
            layers = {v.name: LayerPlan(name=v.name, stage=stage[v.name])
                      for v in g.vertices()}
            streams = [StreamPlan(e.src, e.dst,
                                  evicted=e.buffer_depth > 4096.0,
                                  codec="bfp8" if e.buffer_depth > 4096.0
                                  else "none")
                       for e in g.edges()]
            plan = ExecutionPlan(model=g.name, device="t", n_stages=S,
                                 layers=layers, streams=streams,
                                 topo_order=topo)
            B = 6
            xs = jax.random.normal(jax.random.PRNGKey(1), (B, 64, 32),
                                   jnp.float32)
            sx = lower_plan_pipelined(g, plan, microbatches=B,
                                      kernel_mode="reference",
                                      placement="shard_map")
            assert sx.placement == "shard_map", sx.placement
            low = lower_plan(g, plan, kernel_mode="reference")
            want = np.stack([np.asarray(low(xs[b])) for b in range(B)])
            np.testing.assert_allclose(np.asarray(sx(xs)), want,
                                       rtol=1e-5, atol=1e-6)
            print("OK")
        """)
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.path.join(REPO, "src"))
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout

    def test_shard_map_refused_without_devices(self):
        if len(jax.devices()) >= 2:
            pytest.skip("host has multiple devices")
        g = build_unet_exec()
        plan = _staged_plan(g)
        with pytest.raises(ValueError, match="devices"):
            lower_plan_pipelined(g, plan, microbatches=4,
                                 kernel_mode="reference",
                                 placement="shard_map")
