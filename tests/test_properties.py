"""Hypothesis property tests for system-level invariants of the SMOF core."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import Graph, U200, Vertex
from repro.core.partition import initial_partition, latency_s, merge
from repro.core.pipeline import (initiation_interval, pipeline_depth,
                                 vertex_delays)


def chain(n, macs, depths):
    g = Graph("c")
    g.add(Vertex("in", "input", in_words=64, out_words=64))
    prev = "in"
    for i in range(n):
        g.add(Vertex(f"v{i}", "conv", work_macs=float(macs[i]),
                     weight_words=100, in_words=64, out_words=64,
                     base_depth=float(depths[i]), max_par=16))
        g.connect(prev, f"v{i}")
        prev = f"v{i}"
    return g


@given(st.integers(1, 6),
       st.lists(st.integers(100, 100_000), min_size=6, max_size=6),
       st.lists(st.integers(1, 500), min_size=6, max_size=6))
@settings(max_examples=30, deadline=None)
def test_pipeline_depth_positive_and_bounded(n, macs, depths):
    g = chain(n, macs, depths)
    d = pipeline_depth(g)
    assert d > 0
    # upper bound: every vertex at its worst-case initiation rate
    rates = {v: max(1e-12, 64 / max(m + 10, 64))
             for v, m in zip([f"v{i}" for i in range(n)], macs)}
    assert np.isfinite(d)


@given(st.lists(st.integers(1_000, 1_000_000), min_size=4, max_size=4))
@settings(max_examples=25, deadline=None)
def test_parallelism_never_hurts_ii(macs):
    g = chain(4, macs, [10] * 4)
    ii_before = initiation_interval(g)
    for v in g.vertices():
        v.par = min(v.par * 4, v.max_par)
    assert initiation_interval(g) <= ii_before


@given(st.lists(st.integers(100, 50_000), min_size=5, max_size=5))
@settings(max_examples=25, deadline=None)
def test_delays_monotone_along_chain(macs):
    """Eq. 10: Delay accumulates — downstream >= upstream."""
    g = chain(5, macs, [5] * 5)
    d = vertex_delays(g)
    prev = d["in"]
    for i in range(5):
        assert d[f"v{i}"] >= prev
        prev = d[f"v{i}"]


@given(st.integers(2, 5), st.integers(1, 16))
@settings(max_examples=20, deadline=None)
def test_merge_preserves_vertex_set(n, batch):
    g = chain(n, [1000] * n, [10] * n)
    g.compute_buffer_depths()
    p = initial_partition(g, cut_kinds=None)
    all_v = set(g.g.nodes)
    while p.n > 1:
        p = merge(p, 0)
        assert set(v for part in p.parts for v in part) == all_v
        p.validate()


@given(st.integers(1, 64))
@settings(max_examples=15, deadline=None)
def test_latency_monotone_in_batch(batch):
    g = chain(3, [10_000, 5_000, 2_000], [10, 10, 10])
    g.compute_buffer_depths()
    p = initial_partition(g, cut_kinds=None)
    t1 = latency_s(p, U200, batch)
    t2 = latency_s(p, U200, batch + 1)
    assert t2 >= t1


# =============================================================================
# BFP8 codec properties — the padded path the streamer's queues exercise
# =============================================================================

def _bfp8_block_error_bound(x_flat: np.ndarray, block: int = 32) -> np.ndarray:
    """Per-element worst-case |err|: half the block scale.

    scale = 2^(ceil(log2 amax) - 6) <= amax * 2^-5, and |x| <= amax <= 2^exp
    means no mantissa clipping, so rounding error <= scale/2 <= amax/64."""
    pad = (-x_flat.size) % block
    fp = np.pad(x_flat, (0, pad)).reshape(-1, block)
    amax = np.abs(fp).max(axis=1)
    return np.repeat(amax / 64.0 + 1e-12, block)[: x_flat.size]


@given(st.integers(1, 6), st.integers(1, 97), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=30, deadline=None)
def test_bfp8_roundtrip_error_bound_and_shape_any_channels(rows, cols, seed):
    """encode->decode keeps the shape for ANY (rows, cols) — channel counts
    that are not multiples of the block included — and every element lands
    within the shared-exponent quantisation bound."""
    from repro.core.compression import bfp8_decode, bfp8_encode

    rng = np.random.default_rng(seed)
    x = (10.0 * rng.standard_normal((rows, cols))).astype(np.float32)
    enc = bfp8_encode(x, block=32)
    dec = bfp8_decode(enc)
    assert dec.shape == x.shape and dec.dtype == np.float32
    err = np.abs(dec - x).ravel()
    assert np.all(err <= _bfp8_block_error_bound(x.ravel()))


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_bfp8_all_zero_blocks_roundtrip_exactly(seed):
    rng = np.random.default_rng(seed)
    from repro.core.compression import bfp8_decode, bfp8_encode
    x = np.zeros((int(rng.integers(1, 5)), int(rng.integers(1, 70))),
                 np.float32)
    np.testing.assert_array_equal(bfp8_decode(bfp8_encode(x, block=32)), x)


@given(st.integers(1, 8), st.integers(1, 95), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_jax_padded_roundtrip_matches_numpy_codec(m, c, seed):
    """The in-pipeline jax round-trip (pad channels to the block, quantise
    row-blockwise) is shape-invariant for non-block-multiple channel counts
    and agrees with the numpy codec applied to the padded stripe — the
    exact path a streamer queue payload takes."""
    from repro.core.compression import bfp8_decode, bfp8_encode
    from repro.runtime.executor import _bfp8_roundtrip
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((m, c)).astype(np.float32)
    got = np.asarray(_bfp8_roundtrip(jnp.asarray(x), use_pallas=False,
                                     interpret=True))
    assert got.shape == x.shape
    c_pad = ((c + 31) // 32) * 32
    xp = np.pad(x, ((0, 0), (0, c_pad - c)))
    want = bfp8_decode(bfp8_encode(xp, block=32))[:, :c]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # the padded path still honours the per-block error bound row by row
    for r in range(m):
        err = np.abs(got[r] - x[r])
        assert np.all(err <= _bfp8_block_error_bound(xp[r])[:c])


# =============================================================================
# ExecutionPlan serialisation — the compile façade's on-disk artifact
# =============================================================================

_CODECS = ("none", "rle", "huffman", "bfp8")


def _plan_from_draws(n_layers, stages, fracs, codec_ids, tp, extra):
    """Deterministically build a nested plan from integer draws."""
    from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan

    names = [f"v{i}" for i in range(n_layers)]
    cur = 0
    layers = {}
    for i, n in enumerate(names):
        cur = max(cur, stages[i % len(stages)])       # stages non-decreasing
        layers[n] = LayerPlan(
            name=n, stage=cur, tp_parallelism=1 + tp[i % len(tp)],
            weight_static_fraction=fracs[i % len(fracs)] / 8.0,
            weight_stream_codec=_CODECS[codec_ids[i % len(codec_ids)] % 4])
    streams = [StreamPlan(names[i], names[i + 1],
                          evicted=bool(codec_ids[i % len(codec_ids)] % 2),
                          codec=_CODECS[codec_ids[i % len(codec_ids)] % 4])
               for i in range(n_layers - 1)]
    return ExecutionPlan(
        model="prop", device="dev", n_stages=cur + 1, layers=layers,
        streams=streams, remat="none", microbatch=1 + extra,
        est_throughput_fps=extra / 7.0, est_latency_s=extra * 1e-3,
        topo_order=list(names),
        provenance={f"k{i}": i for i in range(extra)})


@given(st.integers(1, 9),
       st.lists(st.integers(0, 3), min_size=4, max_size=4),
       st.lists(st.integers(0, 8), min_size=4, max_size=4),
       st.lists(st.integers(0, 7), min_size=4, max_size=4),
       st.lists(st.integers(0, 3), min_size=4, max_size=4),
       st.integers(0, 5))
@settings(max_examples=30, deadline=None)
def test_plan_json_roundtrip_bit_exact(n_layers, stages, fracs, codec_ids,
                                       tp, extra):
    """to_json -> from_json round-trips nested LayerPlan/StreamPlan
    dataclasses bit-exactly: dataclass-equal AND byte-equal re-serialised."""
    from repro.core.plan import ExecutionPlan, PLAN_SCHEMA_VERSION

    plan = _plan_from_draws(n_layers, stages, fracs, codec_ids, tp, extra)
    s = plan.to_json()
    back = ExecutionPlan.from_json(s)
    assert back == plan                       # nested dataclass equality
    assert back.to_json() == s                # bit-exact on the wire
    assert back.dropped_keys == ()            # nothing migrated away
    assert back.schema_version == PLAN_SCHEMA_VERSION


@given(st.integers(1, 6), st.integers(1, 4))
@settings(max_examples=15, deadline=None)
def test_plan_unknown_keys_are_collected(n_layers, n_extra):
    """Forward-compat is observable: every key a newer writer added is in
    ``dropped_keys`` (per scope), and the known payload is untouched."""
    import json as _json

    from repro.core.plan import ExecutionPlan

    plan = _plan_from_draws(n_layers, [0, 1, 1, 2], [8] * 4, [0] * 4,
                            [0] * 4, 0)
    d = _json.loads(plan.to_json())
    lname = next(iter(d["layers"]))
    expect = set()
    for i in range(n_extra):
        d[f"new{i}"] = i
        expect.add(f"plan.new{i}")
    d["layers"][lname]["new_layer_knob"] = 1
    expect.add(f"layers[{lname}].new_layer_knob")
    if d["streams"]:
        d["streams"][0]["new_stream_knob"] = 2
        expect.add("streams[0].new_stream_knob")
    back = ExecutionPlan.from_json(_json.dumps(d))
    assert set(back.dropped_keys) == expect
    assert back.layers == plan.layers
    assert back.streams == plan.streams


@given(st.integers(1, 6), st.integers(1, 5))
@settings(max_examples=15, deadline=None)
def test_plan_future_schema_version_roundtrips_clean(n_layers, bump):
    """A plan written by a *future* toolflow (higher schema_version plus
    keys this version has never heard of) still loads: the migration is
    recorded in provenance, the unknown keys are collected, and — the
    forward-compat contract — re-serialising does NOT reintroduce them,
    so a second load sees a clean current-version artifact."""
    import json as _json

    from repro.core.plan import ExecutionPlan, PLAN_SCHEMA_VERSION

    plan = _plan_from_draws(n_layers, [0, 0, 1, 1], [8] * 4, [0] * 4,
                            [0] * 4, 0)
    d = _json.loads(plan.to_json())
    d["schema_version"] = PLAN_SCHEMA_VERSION + bump
    d["spill_priority"] = [1, 2, 3]                  # "future" plan knob
    lname = next(iter(d["layers"]))
    d["layers"][lname]["vector_lanes"] = 8           # "future" layer knob
    back = ExecutionPlan.from_json(_json.dumps(d))
    assert back.schema_version == PLAN_SCHEMA_VERSION
    assert (back.provenance["migrated_from_schema_version"]
            == PLAN_SCHEMA_VERSION + bump)
    assert "plan.spill_priority" in back.dropped_keys
    assert f"layers[{lname}].vector_lanes" in back.dropped_keys
    s2 = back.to_json()
    assert "spill_priority" not in s2 and "vector_lanes" not in s2
    again = ExecutionPlan.from_json(s2)
    assert again.dropped_keys == ()                  # second load is clean
    assert again.layers == plan.layers


def test_plan_future_schema_keys_are_logged(caplog):
    """The forward-compat shim is observable: dropping keys logs one
    warning naming every dropped key."""
    import json as _json
    import logging

    from repro.core.plan import ExecutionPlan

    plan = _plan_from_draws(2, [0] * 4, [8] * 4, [0] * 4, [0] * 4, 0)
    d = _json.loads(plan.to_json())
    d["from_the_future"] = True
    with caplog.at_level(logging.WARNING, logger="repro.core.plan"):
        ExecutionPlan.from_json(_json.dumps(d))
    assert any("plan.from_the_future" in r.getMessage()
               for r in caplog.records)


def test_from_json_rejects_backwards_stage_crossing():
    """A plan whose stream runs from a later stage to an earlier one is
    unschedulable; from_json must fail with the typed validation error,
    not hand the plan to the lowering to crash on."""
    import json as _json

    import pytest

    from repro.core.plan import ExecutionPlan, PlanValidationError

    plan = _plan_from_draws(3, [0, 1, 2, 2], [8] * 4, [0] * 4, [0] * 4, 0)
    d = _json.loads(plan.to_json())
    d["layers"]["v0"]["stage"] = 2                   # v0 -> v1 now 2 -> ?
    d["layers"]["v1"]["stage"] = 0                   # ... -> 0: backwards
    with pytest.raises(PlanValidationError, match="backwards"):
        ExecutionPlan.from_json(_json.dumps(d))


def test_from_json_rejects_malformed_scalars():
    """Out-of-range stages, fractions and microbatch counts all fail
    validation with every problem named in one error."""
    import json as _json

    import pytest

    from repro.core.plan import ExecutionPlan, PlanValidationError

    plan = _plan_from_draws(2, [0] * 4, [8] * 4, [0] * 4, [0] * 4, 0)
    d = _json.loads(plan.to_json())
    d["layers"]["v0"]["stage"] = 99
    d["layers"]["v1"]["weight_static_fraction"] = 1.5
    d["microbatch"] = 0
    with pytest.raises(PlanValidationError) as ei:
        ExecutionPlan.from_json(_json.dumps(d))
    msg = str(ei.value)
    assert "v0" in msg and "weight_static_fraction" in msg
    assert "microbatch" in msg


# =============================================================================
# Streaming telemetry invariants (ISSUE 6) — random plans driven purely
# through the schedule walk: build_schedule + queue_specs/build_queues +
# StreamTracer, no jit anywhere.
# =============================================================================

import dataclasses as _dc


@_dc.dataclass(frozen=True)
class _Spill:
    """Duck-typed SpillRecord: what StreamTracer/emit_spill_counters read."""
    src: str
    dst: str
    codec: str
    offchip_bits: int


def _random_staged_chain(n, n_stages, chans, skip_draws, stage_draws):
    """A chain with forward skip edges plus a non-decreasing random stage
    assignment — every edge is same-stage or forward-crossing, like any
    valid plan the DSE can emit."""
    g = chain(n, [1000] * n, [10] * n)
    for i, d in enumerate(skip_draws[: max(0, n - 2)]):
        if d:                                      # forward skip v_i -> v_j
            j = i + 2 + d % max(1, n - i - 2)
            if j < n and not g.g.has_edge(f"v{i}", f"v{j}"):
                g.connect(f"v{i}", f"v{j}")
    topo = g.topo()
    steps = [stage_draws[i % len(stage_draws)] % 2 for i in range(len(topo))]
    stage, stage_of = 0, {}
    for name, inc in zip(topo, steps):
        stage = min(n_stages - 1, stage + inc)
        stage_of[name] = stage
    out_shape = {name: (1 + chans[i % len(chans)] % 8,
                        1 + chans[(i + 1) % len(chans)])
                 for i, name in enumerate(topo)}
    return g, stage_of, out_shape


@given(st.integers(2, 6), st.integers(1, 4), st.integers(1, 8),
       st.lists(st.integers(0, 5), min_size=4, max_size=4),
       st.lists(st.integers(0, 63), min_size=4, max_size=4),
       st.lists(st.integers(0, 99), min_size=6, max_size=6))
@settings(max_examples=40, deadline=None)
def test_queue_high_water_bounded_by_eq1_capacity(n, n_stages, B, skips,
                                                  chans, stages):
    """Eq. 1 queue sizing holds on random plans: walking the full 1F1B
    schedule through the bounded rings never exceeds any ring's capacity,
    never stalls, and drains every ring completely."""
    from repro.obs import StreamTracer
    from repro.runtime.streamer import (build_queues, build_schedule,
                                        queue_specs)

    g, stage_of, out_shape = _random_staged_chain(n, n_stages, chans,
                                                  skips, stages)
    specs = queue_specs(g, stage_of, out_shape)
    queues = build_queues(specs)
    sched = build_schedule(max(stage_of.values()) + 1, B)
    acct = StreamTracer(schedule=sched, queues=queues,
                        stage_of=stage_of).run_model()
    assert acct["ticks_run"] == sched.ticks
    for e, s in specs.items():
        st_ = acct["queues"][f"{e[0]}->{e[1]}"]
        assert st_["high_water"] <= s.capacity      # the Eq. 1 bound
        assert st_["high_water"] == min(B, s.delay)  # shift-register depth
        assert st_["push_stalls"] == 0 and st_["pop_stalls"] == 0
        assert st_["occupancy"] == 0                # fully drained


@given(st.integers(2, 6), st.integers(1, 4), st.integers(1, 8),
       st.lists(st.integers(0, 5), min_size=4, max_size=4),
       st.lists(st.integers(0, 63), min_size=4, max_size=4),
       st.lists(st.integers(0, 99), min_size=6, max_size=6),
       st.lists(st.integers(1, 10_000), min_size=5, max_size=5),
       st.lists(st.integers(0, 1), min_size=5, max_size=5))
@settings(max_examples=40, deadline=None)
def test_spill_bytes_conserved_on_random_plans(n, n_stages, B, skips, chans,
                                               stages, sizes, codecs):
    """Every byte evicted off-chip is restored: over any complete 1F1B
    run, ``bytes_evicted == bytes_restored`` per spilled edge (and BFP8
    encode count == decode count) — each endpoint stage is active for
    exactly ``B`` ticks, regardless of plan shape."""
    from repro.obs import StreamTracer, TraceRecorder
    from repro.runtime.streamer import build_schedule

    g, stage_of, _ = _random_staged_chain(n, n_stages, chans, skips, stages)
    names = list(stage_of)
    records = []
    for i, (bits, is_bfp8) in enumerate(zip(sizes, codecs)):
        src = names[i % len(names)]
        dst = names[(i * 3 + 1) % len(names)]
        records.append(_Spill(src=src, dst=dst,
                              codec="bfp8" if is_bfp8 else "none",
                              offchip_bits=8 * bits))
    rec = TraceRecorder(clock=lambda: 0.0)
    sched = build_schedule(max(stage_of.values()) + 1, B)
    StreamTracer(rec, sched, stage_of=stage_of,
                 spill_records=records).run_model()
    per_edge_bytes = {}
    for r in records:
        per_edge_bytes.setdefault(f"{r.src}->{r.dst}", 0)
        per_edge_bytes[f"{r.src}->{r.dst}"] += B * (r.offchip_bits // 8)
    for edge, want in per_edge_bytes.items():
        assert rec.totals[f"spill:{edge}:bytes_evicted"] == want
        assert rec.totals[f"spill:{edge}:bytes_restored"] == want
    for k, v in rec.totals.items():
        if k.startswith("bfp8:") and k.endswith(":encodes"):
            assert v == rec.totals[k.replace(":encodes", ":decodes")]


@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_buffer_depths_nonnegative_any_dag(seed, width):
    """Random DAGs: buffer depths are always >= the double-buffer floor."""
    rng = np.random.default_rng(seed)
    g = Graph("r")
    g.add(Vertex("in", "input", in_words=32, out_words=32))
    names = ["in"]
    for i in range(width):
        v = g.add(Vertex(f"n{i}", "conv",
                         work_macs=float(rng.integers(100, 10_000)),
                         weight_words=10, in_words=32, out_words=32,
                         base_depth=float(rng.integers(1, 100)), max_par=8))
        for parent in rng.choice(names, size=min(2, len(names)),
                                 replace=False):
            g.connect(str(parent), v.name)
        names.append(v.name)
    g.compute_buffer_depths()
    for e in g.edges():
        assert e.buffer_depth >= 2.0


@given(st.lists(st.floats(1e-9, 100.0, allow_nan=False,
                          allow_infinity=False),
                min_size=1, max_size=64),
       st.lists(st.floats(0.0, 1.0, allow_nan=False, allow_infinity=False),
                min_size=2, max_size=8))
@settings(max_examples=40, deadline=None)
def test_latency_histogram_quantile_monotone_and_bounded(values, qs):
    """The serving-layer quantile estimator (ISSUE 7): for any recorded
    sample set, ``quantile(q)`` is monotone non-decreasing in q and every
    estimate lies within [min recorded, max recorded] — the log2-bucket
    upper-edge answer is conservative but never escapes the data."""
    from repro.obs import LatencyHistogram
    h = LatencyHistogram()
    for v in values:
        h.record(v)
    lo, hi = min(values), max(values)
    estimates = [h.quantile(q) for q in sorted(qs)]
    assert estimates == sorted(estimates)
    for est in estimates:
        assert lo <= est <= hi
    s = h.summary()
    assert s["min_s"] == lo and s["max_s"] == hi
    assert s["p50_s"] <= s["p95_s"] <= s["p99_s"] <= hi


# -----------------------------------------------------------------------------
# off-chip channel arbitration (ISSUE 9: repro.memory)
# -----------------------------------------------------------------------------

def _arbitrate(policy, bits, weights=None, gbps=8.0, tick=1024.0):
    """One allocation round over len(bits) eviction streams."""
    from repro.memory import ChannelArbiter, ChannelConfig, OffChipChannel
    ch = OffChipChannel(gbps, freq_mhz=250.0)
    kw = {}
    if weights is not None:
        kw = dict(evict_weight=weights[0], restore_weight=weights[1],
                  weight_fetch_weight=weights[2])
    arb = ChannelArbiter(ch, ChannelConfig(policy=policy, **kw))
    kinds = ("activation-evict", "activation-restore", "weight-fetch")
    for i, b in enumerate(bits):
        arb.register(f"s{i}", kinds[i % 3], stage=i % 4, bits_per_frame=b)
    return arb.allocate(tick)


@given(st.sampled_from(("round-robin", "fixed-priority", "weighted-fair")),
       st.lists(st.integers(0, 5_000_000), min_size=1, max_size=12),
       st.floats(0.1, 64.0, allow_nan=False, allow_infinity=False))
@settings(max_examples=40, deadline=None)
def test_arbiter_work_conserving_and_capacity_bounded(policy, bits, gbps):
    """Every policy (a) never grants past the channel's capacity, (b)
    never grants a stream more than it demands, and (c) is
    work-conserving: while unmet demand remains, the channel is fully
    granted (up to burst-quantisation epsilon)."""
    rep = _arbitrate(policy, bits, gbps=gbps)
    cap = rep.capacity_bits_per_cycle
    eps = 1e-9 * max(1.0, cap)
    assert rep.total_granted_rate <= cap + eps
    for s in rep.streams:
        assert 0.0 <= s.granted_rate <= s.demand_rate + eps
    if rep.total_demand_rate > cap + eps:        # oversubscribed
        assert rep.total_granted_rate >= cap - eps
        assert not rep.feasible
    else:                                        # everyone satisfied
        assert abs(rep.total_granted_rate - rep.total_demand_rate) <= eps
        assert rep.feasible


@given(st.lists(st.integers(1_000, 5_000_000), min_size=3, max_size=9),
       st.floats(0.25, 4.0, allow_nan=False, allow_infinity=False),
       st.floats(1.5, 8.0, allow_nan=False, allow_infinity=False))
@settings(max_examples=40, deadline=None)
def test_weighted_fair_grant_monotone_in_weight(bits, w0, factor):
    """Raising one stream kind's weight (all else fixed) never shrinks
    that kind's aggregate weighted-fair grant."""
    lo = _arbitrate("weighted-fair", bits, weights=(w0, 1.0, 1.0),
                    gbps=0.5)                    # scarce: weights matter
    hi = _arbitrate("weighted-fair", bits, weights=(w0 * factor, 1.0, 1.0),
                    gbps=0.5)
    got_lo = sum(s.granted_rate for s in lo.streams
                 if s.kind == "activation-evict")
    got_hi = sum(s.granted_rate for s in hi.streams
                 if s.kind == "activation-evict")
    assert got_hi >= got_lo - 1e-9


@given(st.lists(st.integers(1_000, 5_000_000), min_size=3, max_size=9))
@settings(max_examples=30, deadline=None)
def test_fixed_priority_starves_low_before_high(bits):
    """Under fixed-priority on a scarce channel, a higher-priority kind
    is never less satisfied than a lower-priority one (priority order:
    weight-fetch > activation-restore > activation-evict)."""
    rep = _arbitrate("fixed-priority", bits, gbps=0.25)
    frac = {}
    for kind in ("weight-fetch", "activation-restore", "activation-evict"):
        ss = [s for s in rep.streams if s.kind == kind and s.demand_rate > 0]
        if ss:
            frac[kind] = (sum(s.granted_rate for s in ss)
                          / sum(s.demand_rate for s in ss))
    order = [k for k in ("weight-fetch", "activation-restore",
                         "activation-evict") if k in frac]
    for hi_k, lo_k in zip(order, order[1:]):
        assert frac[hi_k] >= frac[lo_k] - 1e-9


# =============================================================================
# Streaming-conv fused-codec properties (ISSUE 10) — the fused BFP8
# boundary codec is *defined* to be the unfused three-op pipeline, and
# tile sizes are pure performance knobs.  Hypothesis searches the shape /
# tile / seed space for any counterexample.
# =============================================================================

def _sc_case(m, c, cout, seed):
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, c), jnp.float32)
    w = jax.random.normal(kw, (c, cout), jnp.float32) / np.sqrt(c)
    return x, w


def _assert_reordered_sum(a, b, x, w):
    """``a`` and ``b`` are the same ``x @ w`` up to the order of each
    output's float32 sum.  A tile never changes which products an output
    sums (the full K axis per grid step), but XLA's CPU dot picks its
    kernel, and with it the summation order, by the operands' shapes, so
    off a TPU two tilings may differ by reassociation: at most
    ``2 * K * eps * sum_k |x_k w_k|`` an output."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    bound = 2 * x.shape[1] * np.finfo(np.float32).eps * (np.abs(x) @ np.abs(w))
    diff = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    assert np.all(diff <= bound), (diff.max(), bound.min())


def _sc_encode_ref(y, block=32):
    import jax.numpy as jnp
    from repro.kernels import ref as kref
    c = y.shape[1]
    cp = ((c + block - 1) // block) * block
    return kref.bfp8_quant_ref(jnp.pad(y, ((0, 0), (0, cp - c))),
                               block=block)


@given(st.integers(1, 70), st.integers(1, 70), st.integers(1, 48),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_fused_conv_codec_equals_unfused_pipeline(m, c, cout, seed):
    """decode->conv->encode fused inside one pallas_call is the
    three-dispatch pipeline, for ANY shape: the same activation up to the
    order of its sums (``_assert_reordered_sum``: the fused kernel pads
    the output channels to the codec block, and the CPU's dot sums a
    wider operand in another order), and the payload bitwise the
    encoding of the activation it emits."""
    import jax
    from repro.kernels import ref as kref
    from repro.kernels import streaming_conv as SC

    x, w = _sc_case(m, c, cout, seed)
    payload = _sc_encode_ref(x)
    y_f, pay_f = SC.conv2d(None, w, payload=payload, encode=True,
                           interpret=True)

    xe = kref.bfp8_dequant_ref(*payload, block=32)[:, :c]
    y_u = jax.jit(kref.conv2d_ref)(xe, w)
    _assert_reordered_sum(y_f, y_u, xe, w)
    pay_y = jax.jit(_sc_encode_ref)(y_f)
    np.testing.assert_array_equal(np.asarray(pay_f[0]), np.asarray(pay_y[0]))
    np.testing.assert_array_equal(np.asarray(pay_f[1]), np.asarray(pay_y[1]))


@given(st.integers(1, 70), st.integers(1, 70), st.integers(1, 48),
       st.integers(1, 160), st.integers(1, 160),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_conv_tile_size_independence(m, c, cout, bm, bc, seed):
    """Any (bm, bc) draw — dividing the axes or not, bigger than them or
    not — sums the same products as the default tiling, so the results
    agree up to the order of each sum (``_assert_reordered_sum``)."""
    from repro.kernels import streaming_conv as SC

    x, w = _sc_case(m, c, cout, seed)
    base = SC.conv2d(x, w, interpret=True)
    tiled = SC.conv2d(x, w, bm=bm, bc=bc, interpret=True)
    _assert_reordered_sum(base, tiled, x, w)


@given(st.integers(1, 60), st.integers(1, 60), st.integers(1, 128),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_dwconv_tile_size_independence(m, c, bm, seed):
    """The halo-read dwconv grid: any row-block size, same bits (tap sums
    are evaluated per output row — tiling cannot reassociate them)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import streaming_conv as SC

    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, c), jnp.float32)
    w = jax.random.normal(kw, (3, c), jnp.float32)
    base = SC.dwconv(x, w, interpret=True)
    tiled = SC.dwconv(x, w, bm=bm, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tiled))
