"""The jitted pipelined executor: stages overlap over a microbatch stream.

``lower_plan_pipelined`` consumes the same ``core.plan.ExecutionPlan`` (and
the same per-vertex lowering, via ``runtime.executor.analyze_plan`` /
``apply_vertex``) as the sequential executor, but runs the plan's stages as
a software 1F1B pipeline over ``B`` microbatches:

* **single device** (``placement="interleave"``, the default) — one
  ``jax.lax.scan`` over ``T = B + S - 1`` ticks.
  The carry holds, per stage-crossing edge, a shift register of the
  *encoded* spill (BFP8 mantissas + shared exponents for ``bfp8`` streams,
  raw words otherwise): stage ``i`` pushes microbatch ``b``'s encoded spill
  while stage ``i+1`` decodes microbatch ``b-1`` from the other end — the
  paper's two DMA-burst FIFOs as a scan carry.  Every stage reads the
  previous tick's carry, so within a tick all stages are data-independent
  (XLA can fuse/overlap them) and the spill round-trip is off the critical
  path of its own microbatch.

* **one device per stage** (``placement="shard_map"``, asked for
  explicitly) — a ``shard_map`` ring pipeline: each device owns
  one stage, crossing edges live in per-device transit slots that
  ``ppermute`` one hop per tick, so a spill produced on stage ``i`` arrives
  at stage ``k`` exactly ``k - i`` ticks later while both devices compute.

Numerics are identical to the sequential executor per microbatch: the same
codec functions run in the same composition (pad -> quantise -> dequantise
-> slice), only *when* they run changes.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp

from ...core.graph import Graph
from ...core.plan import ExecutionPlan, PlanValidationError
from ...core.resources import ALL_DEVICES
from ...kernels.streamed_matmul import _round_up
from ...memory import ChannelConfig, MemoryModel, build_memory_model
from ...obs.modelcheck import ModelCheck, check_stream
from ...obs.stream import StreamTracer
from ...obs.trace import NULL_RECORDER, scope
from ..executor import (BFP8_BLOCK, LANES, PlanAnalysis, SpillReport,
                        _exec_spec, _make_offchip_hop, analyze_plan,
                        bfp8_spill_decode, bfp8_spill_encode, init_params,
                        resolve_kernel_mode, run_vertices, weight_shape)
from . import queues as Q
from . import schedule as SCH

PLACEMENTS = ("interleave", "shard_map")

# =============================================================================
# StreamReport
# =============================================================================

@dataclasses.dataclass
class StreamReport(SpillReport):
    """SpillReport plus the pipeline's schedule/occupancy accounting.

    The spill records (and therefore all bit volumes) are the *same objects*
    the sequential executor would report for this plan — per microbatch,
    bit-exact — with the pipeline view stacked on top: per-stage occupancy
    and stall (bubble) counts, per-queue high-water marks, and the Eq. 5 vs
    Eq. 6 frame-time estimates from the stage latency model, so benchmarks
    can show which stage sets ``max_j(L_j)``.
    """
    n_stages: int = 1
    microbatches: int = 1
    ticks: int = 1
    placement: str = "interleave"
    stage_occupancy: list[float] = dataclasses.field(default_factory=list)
    stage_stalls: list[int] = dataclasses.field(default_factory=list)
    stage_latency: list[float] = dataclasses.field(default_factory=list)
    queue_stats: dict = dataclasses.field(default_factory=dict)
    #: output assembly (``placement="interleave"``): the scan's stacked
    #: buffer ``(ticks, n, 128)``, the zeros padded onto each frame's output
    #: to fill it, and the HBM bytes a call writes there plus the one
    #: relayout to ``(B, L)`` reads and writes; ``None`` and 0 on the ring
    emit_rows: tuple[int, int, int] | None = None
    emit_pad: int = 0
    emit_bytes_per_call: int = 0
    #: the off-chip channel view (``repro.memory``) when the plan was
    #: lowered with a :class:`~repro.memory.ChannelConfig`; ``None`` keeps
    #: every contended property degrading to its uncontended twin.
    memory: MemoryModel | None = None

    @property
    def eq5_time(self) -> float:
        """Sequential frame time: sum of stage latencies (Eq. 5)."""
        return SCH.eq5_sequential_time(self.stage_latency)

    @property
    def eq6_time(self) -> float:
        """Pipelined steady-state frame time: slowest stage (Eq. 6)."""
        return SCH.eq6_pipeline_time(self.stage_latency)

    @property
    def bottleneck_stage(self) -> int:
        return max(range(len(self.stage_latency)),
                   key=lambda j: self.stage_latency[j])

    # -- contended (channel-arbitrated) views --------------------------------
    @property
    def stage_latency_contended(self) -> list[float]:
        """``max(L_j, X_j)`` per stage; ``stage_latency`` without a model."""
        if self.memory is None:
            return list(self.stage_latency)
        return list(self.memory.contended_latencies)

    @property
    def eq5_contended_time(self) -> float:
        return SCH.eq5_sequential_time(self.stage_latency_contended)

    @property
    def eq6_contended_time(self) -> float:
        return SCH.eq6_pipeline_time(self.stage_latency_contended)

    @property
    def contention_stall_cycles(self) -> list[float]:
        """Per-stage channel-stall cycles per frame (empty: no model)."""
        return [] if self.memory is None else list(self.memory.stall_cycles)

    @property
    def prefetch_deadline_misses(self) -> int:
        return 0 if self.memory is None else self.memory.prefetch.deadline_misses

    @property
    def channel_policy(self) -> str | None:
        return None if self.memory is None else self.memory.config.policy

    def summary(self) -> dict:
        out = super().summary()
        out.update({
            "n_stages": self.n_stages,
            "microbatches": self.microbatches,
            "ticks": self.ticks,
            "placement": self.placement,
            "stage_occupancy": self.stage_occupancy,
            "stage_stalls": self.stage_stalls,
            "eq5_time": self.eq5_time,
            "eq6_time": self.eq6_time,
            "bottleneck_stage": self.bottleneck_stage,
            "emit_rows": self.emit_rows,
            "emit_pad": self.emit_pad,
            "emit_bytes_per_call": self.emit_bytes_per_call,
        })
        if self.memory is not None:
            out.update({
                "channel_policy": self.channel_policy,
                "eq5_contended_time": self.eq5_contended_time,
                "eq6_contended_time": self.eq6_contended_time,
                "contention_stall_cycles": self.contention_stall_cycles,
                "prefetch_deadline_misses": self.prefetch_deadline_misses,
                "memory": self.memory.summary(),
            })
        return out


# =============================================================================
# Encoded carry codecs (the queue payload)
# =============================================================================

def _codec_pair(codec: str, shape: tuple[int, int], *, use_pallas: bool,
                interpret: bool, dtype=jnp.float32):
    """(encode, decode, zero_template) for one crossing edge's payload.

    ``bfp8``: the carry holds the actual spill buffers (int8 mantissas +
    per-block int8 shared exponents), built from the *same* encode/decode
    halves the sequential executor composes into ``_bfp8_roundtrip`` — the
    two executors' codec numerics are one implementation.  Everything else
    carries raw words (lossless codecs shrink bits, not numbers).
    """
    m, c = shape
    if codec == "bfp8":
        enc = functools.partial(bfp8_spill_encode, use_pallas=use_pallas,
                                interpret=interpret)
        dec = functools.partial(bfp8_spill_decode, c=c, use_pallas=use_pallas,
                                interpret=interpret, dtype=dtype)
        c_pad = _round_up(c, BFP8_BLOCK)
        zero = (jnp.zeros((m, c_pad), jnp.int8),
                jnp.zeros((m, c_pad // BFP8_BLOCK), jnp.int8))
        return enc, dec, zero
    return (lambda x: x), (lambda p: p), jnp.zeros((m, c), dtype)


# =============================================================================
# Stage splitting
# =============================================================================

def _stage_names(an: PlanAnalysis) -> list[list[str]]:
    """Vertices per stage, in graph topological order (the deterministic
    schedule the streamer needs — plan.stage_layers agrees when the plan
    carries its topo_order)."""
    n = an.n_stages
    names: list[list[str]] = [[] for _ in range(n)]
    for v in an.topo:
        names[an.stage_of[v]].append(v)
    for j, ns in enumerate(names):
        if not ns:
            raise PlanValidationError(
                f"stage {j} is empty — plan stages must be "
                f"contiguous 0..{n - 1}")
    return names


def _crossing_edges(g: Graph, an: PlanAnalysis) -> list[tuple[str, str]]:
    out = []
    for e in g.edges():
        d = an.stage_of[e.dst] - an.stage_of[e.src]
        if d < 0:
            raise PlanValidationError(
                f"edge {(e.src, e.dst)} goes backwards across "
                f"stages ({an.stage_of[e.src]} -> "
                f"{an.stage_of[e.dst]})")
        if d > 0:
            out.append((e.src, e.dst))
    return out


def _make_stage_fns(g: Graph, an: PlanAnalysis, names: list[list[str]],
                    crossing: list[tuple[str, str]], hop, enc):
    """Per-stage callables with a uniform signature.

    ``hop`` moves the stage's evicted spills, and the payloads it hands to
    later stages, off-chip and back.  A crossing payload's encode is the
    scope ``smof.codec.enc:<src>``, as inside ``run_vertices``.

    ``fn_j(params, x, reads) -> (produced, y)`` where ``reads`` maps every
    crossing edge to its decoded value (stage ``j`` only touches the ones it
    consumes), ``produced`` maps every crossing edge to an encoded payload
    (zeros template for edges other stages produce — uniform pytrees keep
    ``lax.switch`` branches legal), and ``y`` is the graph output (zeros
    except on the last stage).
    """
    S = an.n_stages
    out_vertex = an.topo[-1]
    out_len = sum(an.out_shape[e.src][0] * an.out_shape[e.src][1]
                  for e in g.in_edges(out_vertex))
    produced_by = {e: an.stage_of[e[0]] for e in crossing}

    def make(j: int):
        mine = set(names[j])

        def fn(params, x, reads):
            # the same payload-routed vertex loop the sequential executor
            # traces (fused BFP8 codec in pallas mode, spill_fn round-trips
            # in reference mode); crossing reads arrive pre-decoded
            values, payloads = run_vertices(
                g, an, names[j], params, x, lambda edge: reads[edge], hop)
            produced = {}
            for e in crossing:
                if produced_by[e] == j:
                    # a pallas-mode producer already emitted this edge's
                    # spill payload (fused egress where _lower_vertex
                    # allowed) — bitwise what enc[e] would compute
                    pay = payloads.get(e[0]) if e in an.bfp8_edges else None
                    if pay is None:
                        with scope("codec.enc", e[0]):
                            pay = enc[e](values[e[0]])
                    produced[e] = jax.tree.map(hop, pay)
                else:
                    produced[e] = None       # filled with zeros by caller
            y = (values[out_vertex] if out_vertex in mine
                 else jnp.zeros((out_len,), jnp.float32))
            return produced, y
        return fn

    return [make(j) for j in range(S)], out_len


# =============================================================================
# Lowered streaming pipeline
# =============================================================================

@dataclasses.dataclass
class StreamingExecutor:
    """A jitted pipelined form of one ExecutionPlan.

    ``fn(params, xs)`` maps a ``(B,) + frame`` microbatch stream (a frame
    is ``(m, c)``, or ``(H, W, c)`` for a spatial graph) to ``(B, L)``
    outputs, bit-for-bit the outputs of running the sequential executor on
    each microbatch independently (modulo nothing: the same codecs run in
    the same composition).  ``stage_fns`` are the individually-jitted
    per-stage callables — the sequential decomposition the pipeline
    overlaps — used by :func:`measured_stage_latencies`.
    """
    fn: Callable[[dict, jax.Array], jax.Array]
    params: dict[str, jax.Array]
    report: StreamReport
    plan: ExecutionPlan | None
    graph_name: str
    n_stages: int
    microbatches: int
    placement: str
    stage_fns: list[Callable]
    _zero_reads: Callable[[], dict]
    _decoders: dict
    _crossing: list[tuple[str, str]]
    schedule: SCH.PipelineSchedule | None = None
    _tick_fn: Callable | None = None
    _carry0: Callable[[], dict] | None = None
    _queue_specs: dict = dataclasses.field(default_factory=dict)
    _stage_of: dict = dataclasses.field(default_factory=dict)
    _stream_shape: tuple = ()

    def __call__(self, xs: jax.Array) -> jax.Array:
        return self.fn(self.params, xs)

    def zero_reads(self) -> dict:
        """A zeros-filled decoded-reads template (for driving stage_fns)."""
        return self._zero_reads()

    def run_traced(self, xs: jax.Array, recorder=NULL_RECORDER, *,
                   measure_stages: bool = True, repeats: int = 3,
                   warmup: int = 1,
                   metrics=None) -> tuple[jax.Array, ModelCheck]:
        """Run the pipeline tick-by-tick, narrating each tick into a trace.

        Same jitted tick body as the fused ``lax.scan`` — the only change
        is *when* host control returns, so outputs are bit-for-bit ``fn``'s
        (asserted by the no-op parity test).  Per tick the host records the
        wall-clock interval, the :class:`~repro.obs.StreamTracer` emits the
        tick/stage spans and walks the bounded queues, and the spill
        counters account each crossing's off-chip bytes.  Returns the
        ``(B, L)`` outputs plus a :class:`~repro.obs.ModelCheck` comparing
        the walk (and, with ``measure_stages``, per-stage wall clock via
        :func:`measured_stage_latencies`) against Eq. 5/6 and Eq. 1.

        Instrumentation is host-side only, at tick boundaries: with the
        default ``NULL_RECORDER`` every hook is a no-op and the jitted
        computation is untouched.  With a ``metrics``
        :class:`~repro.obs.metrics.MetricsRegistry`, the run additionally
        feeds the scrape surface: per-phase ``smof_stream_ticks_total``,
        ``smof_stream_frames_total``, per-edge queue occupancy/stall
        metrics (via the rings) and ``smof_spill_bytes_total``.
        """
        import time

        if self._tick_fn is None:
            raise NotImplementedError(
                f"traced execution requires 'interleave' placement, "
                f"this executor is {self.placement!r}")
        if tuple(xs.shape) != self._stream_shape:
            raise ValueError(
                f"microbatch stream shape {tuple(xs.shape)} does not match "
                f"the lowered {self._stream_shape} for {self.graph_name!r}")
        sched = self.schedule
        queues = Q.build_queues(self._queue_specs, recorder, metrics)
        tracer = StreamTracer(recorder, sched, queues=queues,
                              stage_of=self._stage_of,
                              spill_records=self.report.spills)
        # compile warmup on a throwaway carry so tick 0's span measures the
        # tick, not XLA compilation
        warm = self._tick_fn(self.params, self._carry0(),
                             jnp.asarray(0, jnp.int32), xs)
        jax.block_until_ready(warm)

        carry = self._carry0()
        ys = []
        steady_durs: list[float] = []
        for t in range(sched.ticks):
            ts = recorder.now()
            t0 = time.perf_counter()
            carry, y = self._tick_fn(self.params, carry,
                                     jnp.asarray(t, jnp.int32), xs)
            jax.block_until_ready(y)
            jax.block_until_ready(carry)
            dur = time.perf_counter() - t0
            ys.append(y)
            tracer.tick(t, ts=ts, dur=dur)
            if sched.phase(t) == "steady":
                steady_durs.append(dur)
        acct = tracer.finish()
        if metrics is not None:
            self._record_metrics(metrics, acct)

        stage_s = None
        if measure_stages:
            stage_s = measured_stage_latencies(
                self, xs[0], repeats=repeats, warmup=warmup)
        steady_s = None
        if steady_durs:
            steady_durs.sort()
            steady_s = steady_durs[len(steady_durs) // 2]
        mc = check_stream(self.report, stage_seconds=stage_s,
                          queue_stats=acct["queues"],
                          ticks_measured=acct["ticks_run"],
                          steady_measured=acct["phase_ticks"]["steady"],
                          steady_tick_seconds=steady_s)
        return jnp.stack(ys)[self.n_stages - 1:], mc

    def _record_metrics(self, metrics, acct: dict) -> None:
        """Feed one traced run's accounting into a MetricsRegistry.

        Queue occupancy/stall metrics update live inside the rings (they
        were built with the registry); what is left to record at run end
        are tick counts and the per-edge off-chip spill volume — each
        spill record moves ``offchip_bits`` once per microbatch, the same
        totals ``StreamTracer`` accumulates on the recorder.
        """
        ticks = metrics.counter(
            "smof_stream_ticks_total",
            "pipeline ticks walked, by 1F1B phase", ("phase",))
        for phase, n in acct["phase_ticks"].items():
            if n:
                ticks.labels(phase=phase).inc(n)
        metrics.counter(
            "smof_stream_frames_total",
            "microbatch frames retired by the pipelined streamer",
        ).inc(self.microbatches)
        spill = metrics.counter(
            "smof_spill_bytes_total",
            "off-chip spill traffic in bytes, by edge and direction",
            ("edge", "direction"))
        for r in self.report.spills:
            nbytes = (r.offchip_bits // 8) * self.microbatches
            if nbytes:
                edge = f"{r.src}->{r.dst}"
                spill.labels(edge=edge, direction="evict").inc(nbytes)
                spill.labels(edge=edge, direction="restore").inc(nbytes)


def stage_weight_bits(g: Graph, an: PlanAnalysis) -> dict[int, int]:
    """Streamed weight bits per stage, mirroring ``analyze_plan``'s
    per-layer rounding exactly so the per-stage sums equal
    ``streamed_weight_bits`` bit-for-bit (the channel model's byte
    conservation depends on it)."""
    out = {j: 0 for j in range(an.n_stages)}
    for name, f in an.frac.items():
        v = g.vertex(name)
        wbits = (math.prod(weight_shape(v.kind, _exec_spec(g, name)))
                 * v.weight_bits)
        out[an.stage_of[name]] += int(round((1.0 - f) * wbits))
    return out


def _resolve_channel_device(channel: ChannelConfig,
                            device, plan: ExecutionPlan
                            ) -> tuple[float, float] | None:
    """(gbps, freq_mhz) for the channel model, or ``None`` when nothing
    prices the port.  Resolution order: the config's explicit override,
    then the ``device`` argument (a registry name or a ``Device``-like
    object), then the plan's recorded device name."""
    dev = None
    if isinstance(device, str):
        dev = ALL_DEVICES.get(device)
    elif device is not None:
        dev = device
    if dev is None:
        dev = ALL_DEVICES.get(plan.device)
    if dev is not None:
        gbps = channel.gbps if channel.gbps is not None else dev.offchip_gbps
        return gbps, dev.freq_mhz
    if channel.gbps is not None:
        return channel.gbps, 200.0      # Device's default clock
    return None


def lower_plan_pipelined(g: Graph, plan: ExecutionPlan, *,
                         microbatches: int | None = None,
                         kernel_mode: str = "auto", seed: int = 0,
                         interpret: bool | None = None,
                         placement: str = "interleave",
                         channel: ChannelConfig | None = None,
                         device=None) -> StreamingExecutor:
    """Lower ``plan`` over ``g`` to a pipelined multi-microbatch executor.

    microbatches: length ``B`` of the input stream the jitted step is traced
    for (defaults to ``plan.microbatch``, floored at 1).
    placement: "interleave" (single-device scan) or "shard_map" (one stage
    per device).  The choice is the caller's: a host with spare devices
    does not move a plan onto the ring by itself.
    channel: opt-in off-chip channel model (``repro.memory``): the plan's
    streams are arbitrated over the shared port, queue capacities absorb
    the arbiter-derived crossing delays, and the report carries the
    contended Eq. 5/6 bounds plus the prefetch deadline accounting.
    device: registry name or ``Device`` pricing the channel (defaults to
    ``plan.device``); without a resolvable device *and* no explicit gbps
    override the channel model is skipped.
    """
    use_pallas, interpret = resolve_kernel_mode(kernel_mode, interpret)
    B = int(microbatches if microbatches is not None
            else max(plan.microbatch, 1))
    if B < 1:
        raise ValueError(f"need >= 1 microbatch, got {B}")

    an = analyze_plan(g, plan, use_pallas=use_pallas, interpret=interpret)
    S = an.n_stages
    names = _stage_names(an)
    crossing = _crossing_edges(g, an)
    sched = SCH.build_schedule(S, B)
    hop = _make_offchip_hop()

    if placement not in PLACEMENTS:
        raise ValueError(f"unknown placement {placement!r}; pick one of "
                         f"{PLACEMENTS}")
    if placement == "shard_map" and len(jax.devices()) < S:
        raise ValueError(f"shard_map placement needs >= {S} devices, "
                         f"have {len(jax.devices())}")

    stream_map = {(s.src, s.dst): s for s in plan.streams}
    codec_of = {e: (stream_map[e].codec
                    if e in stream_map and stream_map[e].evicted else "none")
                for e in crossing}
    enc: dict = {}
    dec: dict = {}
    zeros: dict = {}
    for e in crossing:
        enc[e], dec[e], zeros[e] = _codec_pair(
            codec_of[e], an.out_shape[e[0]], use_pallas=use_pallas,
            interpret=interpret)

    stage_fns, out_len = _make_stage_fns(g, an, names, crossing, hop, enc)
    delay = {e: an.stage_of[e[1]] - an.stage_of[e[0]] for e in crossing}

    def fill_zeros(produced: dict) -> dict:
        return {e: (zeros[e] if produced[e] is None else produced[e])
                for e in crossing}

    # -- single-device interleave: lax.scan over the tick axis ---------------
    # tick_body is shared between the fused scan (build_interleave) and the
    # per-tick traced loop (StreamingExecutor.run_traced): one definition,
    # so the traced run cannot drift numerically from the fast path.
    def make_carry0() -> dict:
        return {e: jax.tree.map(
            lambda z, d=delay[e]: jnp.zeros((d,) + z.shape, z.dtype),
            zeros[e]) for e in crossing}

    # Scopes: ``smof.tick`` (one tick's work), inside it ``smof.tick.read``
    # (the tick's frame and crossing decodes) and ``smof.tick.carry`` (the
    # delay-line shift); ``smof.emit`` (around the scan, so that only the
    # scan's stacking of each tick's output and the one relayout of the
    # stack into the ``(B, L)`` result have it innermost).
    def tick_body(params, carry, t, xs):
        with scope("tick"):
            with scope("tick.read"):
                x_t = jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, B - 1), axis=0, keepdims=False)
                reads = {e: dec[e](jax.tree.map(lambda b: b[-1], carry[e]))
                         for e in crossing}
            produced: dict = {}
            y = jnp.zeros((out_len,), jnp.float32)
            for j in range(S):
                prod_j, y_j = stage_fns[j](params,
                                           x_t if j == 0 else None, reads)
                for e in crossing:
                    if prod_j[e] is not None:
                        produced[e] = prod_j[e]
                if j == S - 1:
                    y = y_j
            with scope("tick.carry"):
                new_carry = {
                    e: jax.tree.map(
                        lambda buf, new: jnp.concatenate(
                            [new[None], buf[:-1]], axis=0),
                        carry[e], produced[e])
                    for e in crossing}
            return new_carry, y

    # Output assembly: each tick's ``y`` is stacked as ``(n, 128)`` rows of
    # whole (8, 128) tiles, zero-padded where ``L`` is not a multiple of
    # 1024, so a tick's write covers tiles of its own.  Stacked as ``(L,)``
    # rows, one tick would touch a sublane of every tile of the
    # ``(ticks, L)`` buffer, and XLA rewrites all of it each tick.
    n_emit = -(-out_len // (8 * LANES)) * 8
    pad_emit = n_emit * LANES - out_len

    def as_tiles(y):
        if pad_emit:
            y = jnp.pad(y, (0, pad_emit))
        return y.reshape(n_emit, LANES)

    def build_interleave():
        def step(params, xs):
            _check_stream_shape(xs)

            def tick(c, t):
                c, y = tick_body(params, c, t, xs)
                return c, as_tiles(y)
            with scope("emit"):
                _, ys = jax.lax.scan(tick, make_carry0(),
                                     jnp.arange(sched.ticks))
                ys = ys[S - 1:].reshape(B, n_emit * LANES)
                return ys[:, :out_len] if pad_emit else ys
        return jax.jit(step)

    # -- multi-device ring: shard_map, one stage per device ------------------
    # XLA moves values to host memory only outside conditionals, and each
    # device picks its stage with ``lax.switch``: the crossing payloads hop
    # after the switch (every device hops every transit slot), and a spill
    # evicted *within* a stage cannot leave HBM on this placement.
    def build_shard_map():
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        if jax.devices()[0].platform == "tpu":   # where the hop is real
            inner = [e for e in an.spills
                     if e.reason == "evicted"
                     and an.stage_of[e.src] == an.stage_of[e.dst]]
            if inner:
                raise ValueError(
                    f"shard_map placement cannot move spills evicted "
                    f"inside a stage to host memory "
                    f"({', '.join(f'{e.src}->{e.dst}' for e in inner)}); "
                    f"cut the stages at those edges or use 'interleave'")
        ring_fns, _ = _make_stage_fns(g, an, names, crossing,
                                      lambda x: x, enc)
        mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
        perm = [(i, (i + 1) % S) for i in range(S)]

        def body(params, xs):
            j = jax.lax.axis_index("stage")

            def tick(carry, t):
                with scope("tick"):
                    with scope("tick.read"):
                        x_t = jax.lax.dynamic_index_in_dim(
                            xs, jnp.clip(t, 0, B - 1), axis=0,
                            keepdims=False)
                        reads = {e: dec[e](jax.tree.map(lambda b: b[0],
                                                        carry[e]))
                                 for e in crossing}

                    def branch(jj):
                        def f(params, x_t, reads):
                            prod, y = ring_fns[jj](
                                params, x_t if jj == 0 else None, reads)
                            return fill_zeros(prod), y
                        return f
                    produced, y = jax.lax.switch(
                        j, [branch(jj) for jj in range(S)], params, x_t,
                        reads)
                    produced = jax.tree.map(hop, produced)
                    new_carry = {}
                    with scope("tick.carry"):
                        for e in crossing:
                            i_prod = an.stage_of[e[0]]
                            slot = jax.tree.map(
                                lambda old, new: jnp.where(j == i_prod,
                                                           new[None], old),
                                carry[e], produced[e])
                            new_carry[e] = jax.tree.map(
                                lambda s: jax.lax.ppermute(s, "stage", perm),
                                slot)
                    return new_carry, y

            carry0 = {e: jax.tree.map(lambda z: z[None], zeros[e])
                      for e in crossing}
            with scope("emit"):
                _, ys = jax.lax.scan(tick, carry0, jnp.arange(sched.ticks))
                # only the last stage computed real outputs; share them
                ys = jnp.where(j == S - 1, ys, 0.0)
                return jax.lax.psum(ys, "stage")

        smap = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_vma=False)

        def step(params, xs):
            _check_stream_shape(xs)
            ys = smap(params, xs)
            with scope("emit"):
                return ys[S - 1:]
        return jax.jit(step)

    def _check_stream_shape(xs):
        if tuple(xs.shape) != (B,) + an.in_shape:
            raise ValueError(
                f"microbatch stream shape {tuple(xs.shape)} does not match "
                f"the lowered ({B}, *{an.in_shape}) for {g.name!r}")

    fn = build_shard_map() if placement == "shard_map" else build_interleave()

    # -- report: schedule + bounded-queue accounting --------------------------
    lat = SCH.stage_latencies(g, plan)
    mem = None
    if channel is not None:
        priced = _resolve_channel_device(channel, device, plan)
        if priced is not None:
            gbps, freq_mhz = priced
            mem = build_memory_model(
                spills=an.spills,
                weight_bits_by_stage=stage_weight_bits(g, an),
                stage_of=an.stage_of, base_latencies=lat,
                gbps=gbps, freq_mhz=freq_mhz, config=channel,
                microbatches=B)
    specs = Q.queue_specs(
        g, an.stage_of, an.out_shape, codec_of,
        extra_delay=(mem.extra_queue_delay() if mem is not None else None))
    sim = SCH.simulate_schedule(
        sched, Q.build_queues(specs),
        producer_stage={e: an.stage_of[e[0]] for e in specs},
        consumer_stage={e: an.stage_of[e[1]] for e in specs})
    base = an.report()
    report = StreamReport(
        spills=base.spills, streamed_weight_bits=base.streamed_weight_bits,
        static_weight_bits=base.static_weight_bits,
        n_stages=S, microbatches=B, ticks=sched.ticks, placement=placement,
        stage_occupancy=sim["stage_occupancy"],
        stage_stalls=sim["stage_stalls"], stage_latency=lat,
        queue_stats={f"{u}->{w}": st
                     for (u, w), st in sim["queues"].items()},
        memory=mem)
    if placement == "interleave":
        # ticks rows stacked, then B rows read and written by the relayout
        report.emit_rows = (sched.ticks, n_emit, LANES)
        report.emit_pad = pad_emit
        report.emit_bytes_per_call = (sched.ticks + 2 * B) * n_emit * LANES * 4

    params = init_params(g, seed=seed)
    jitted_stage_fns = [jax.jit(functools.partial(_stage_call, f))
                        for f in stage_fns]

    def zero_reads():
        return {e: dec[e](zeros[e]) for e in crossing}

    return StreamingExecutor(
        fn=fn, params=params, report=report, plan=plan, graph_name=g.name,
        n_stages=S, microbatches=B, placement=placement,
        stage_fns=jitted_stage_fns, _zero_reads=zero_reads, _decoders=dec,
        _crossing=crossing, schedule=sched,
        _tick_fn=(jax.jit(tick_body) if placement == "interleave" else None),
        _carry0=make_carry0, _queue_specs=specs, _stage_of=dict(an.stage_of),
        _stream_shape=(B,) + an.in_shape)


def _stage_call(stage_fn, params, x, reads):
    """Uniform jit wrapper: drop the None placeholders so each stage's
    jitted signature only contains arrays."""
    prod, y = stage_fn(params, x, reads)
    return {e: p for e, p in prod.items() if p is not None}, y


# =============================================================================
# Measured per-stage latencies (the Eq. 5/6 hook, wall-clock edition)
# =============================================================================

def measured_stage_latencies(sx: StreamingExecutor, x: jax.Array, *,
                             repeats: int = 5, warmup: int = 2
                             ) -> list[float]:
    """Wall-clock seconds per stage, dispatched stage-by-stage.

    This is what the *sequential* schedule pays per frame: each stage is a
    separate device dispatch fed through the decoded reads.  Feeding stage
    ``j+1`` with stage ``j``'s real outputs keeps shapes and codec work
    identical to the pipeline's steady state.  Plug the result into the
    Eq. 5/6 estimators to place measured pipeline throughput between the
    sequential sum and the slowest-stage bound.
    """
    import time

    reads = sx.zero_reads()
    lat: list[float] = []
    for j, fn in enumerate(sx.stage_fns):
        x_j = x if j == 0 else None

        def call():
            prod, y = fn(sx.params, x_j, reads)
            jax.block_until_ready((prod, y))
            return prod, y

        for _ in range(warmup):
            prod, _ = call()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            prod, _ = call()
            times.append(time.perf_counter() - t0)
        times.sort()
        lat.append(times[len(times) // 2])
        # thread this stage's real (decoded) outputs into the next reads
        for e, payload in prod.items():
            reads[e] = sx._decoders[e](payload)
    return lat
