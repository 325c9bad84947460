"""Batched serving engine with continuous batching and SMOF cache eviction.

SMOF is an inference toolflow, so this is the system's end-to-end driver:
requests enter a queue, get packed into fixed decode slots (continuous
batching — a finished request's slot is immediately refilled), prefill runs
per-request, and decode advances all active slots in lockstep.

The paper's activation eviction shows up here as **KV-page eviction**: when
a slot's cache page goes cold (its request finished) or the configured
residency budget is exceeded, pages are evicted to the host in BFP8 (the
§V-A codec) and restored on demand — Eq. 1/2's on-chip <-> off-chip trade
with HBM as "on-chip" and host DRAM as "off-chip".  ``resident_limit``
keeps the most recently finished requests' pages parked in HBM
(restoration is exact and free); older page-sets spill to the host
oldest-first, so the eviction *order* is the retirement order.

``GraphStreamServer`` is the CNN-side counterpart: a batched front-end
that packs submitted frames into fixed-length microbatch streams and runs
them through the pipelined streaming executor (``runtime/streamer``).
``GraphStreamServer.autotuned`` runs the closed-loop autotuner
(``repro.optim.autotune``) first and serves the measured-best plan.
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import bfp8_decode, bfp8_encode
from repro.models import decode_step, forward, init_cache, project_logits
from repro.models.config import ArchConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import host_span


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: int = 16
    eos: int | None = None
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


class _RegistryStats:
    """Base for the registry-backed stats views.

    The engines used to keep hand-rolled stats dataclasses next to the
    metrics; now the :class:`~repro.obs.metrics.MetricsRegistry` is the
    single source of truth and these views are *live reads* of it — the
    legacy attribute surface (``stats.prefills`` etc.) maps each field to
    its metric sample, and ``report()`` is the registry snapshot filtered
    to this front-end's namespace.
    """

    _PREFIX = "smof_"

    def __init__(self, registry: MetricsRegistry) -> None:
        self._registry = registry

    def _value(self, name: str, **labels) -> int:
        fam = self._registry.get(name)
        return int(fam.labels(**labels).value)

    def report(self) -> dict:
        """All of this front-end's samples, from the registry snapshot."""
        return {k: v for k, v in self._registry.snapshot().items()
                if k.startswith(self._PREFIX)}

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.report()})"


class EngineStats(_RegistryStats):
    """Live view of the decode engine's counters (see ``_RegistryStats``)."""

    _PREFIX = "smof_engine_"

    @property
    def prefills(self) -> int:
        return self._value("smof_engine_prefills_total")

    @property
    def decode_steps(self) -> int:
        return self._value("smof_engine_decode_steps_total")

    @property
    def generated(self) -> int:
        return self._value("smof_engine_generated_tokens_total")

    @property
    def evicted_pages(self) -> int:
        return self._value("smof_engine_evicted_pages_total")

    @property
    def restored_pages(self) -> int:
        return self._value("smof_engine_restored_pages_total")

    @property
    def evicted_bytes_raw(self) -> int:
        return self._value("smof_engine_evicted_bytes_total", kind="raw")

    @property
    def evicted_bytes_compressed(self) -> int:
        return self._value("smof_engine_evicted_bytes_total",
                           kind="compressed")


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 4,
                 s_max: int = 256, dtype=jnp.float32,
                 evict_to_host: bool = False, resident_limit: int = 0,
                 sampler: Callable | None = None,
                 metrics: MetricsRegistry | None = None):
        self.cfg = cfg
        self.params = params
        self.B = max_batch
        self.s_max = s_max
        self.dtype = dtype
        self.evict_to_host = evict_to_host
        # retired page-sets allowed to stay parked in HBM before the oldest
        # spills to the host (0 = spill immediately on retire)
        self.resident_limit = resident_limit
        self.sampler = sampler or (lambda logits: jnp.argmax(logits, -1))
        self.cache = init_cache(cfg, max_batch, s_max, dtype=dtype)
        self.slots: list[Request | None] = [None] * max_batch
        self.pos = np.zeros(max_batch, np.int32)
        self.queue: "queue.Queue[Request]" = queue.Queue()
        # every engine counter lives in one MetricsRegistry (own registry by
        # default so engines never cross-talk; pass one to share a scrape
        # surface); self.stats is a live view over it
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self._c_prefills = m.counter(
            "smof_engine_prefills_total", "prompt prefills run")
        self._c_decode = m.counter(
            "smof_engine_decode_steps_total", "lockstep decode steps")
        self._c_generated = m.counter(
            "smof_engine_generated_tokens_total",
            "tokens sampled across all slots")
        self._c_evicted_pages = m.counter(
            "smof_engine_evicted_pages_total",
            "KV pages BFP8-evicted across the HBM -> host boundary")
        self._c_restored_pages = m.counter(
            "smof_engine_restored_pages_total",
            "KV pages restored into HBM (resident or via BFP8 decode)")
        self._c_evicted_bytes = m.counter(
            "smof_engine_evicted_bytes_total",
            "KV eviction traffic in bytes, raw (bf16 words) vs compressed",
            ("kind",))
        self._h_latency = m.histogram(
            "smof_engine_request_latency_seconds",
            "submit -> retire wall clock per request")
        self.stats = EngineStats(m)
        # submit -> retire wall clock per request (log-bucketed); the same
        # LatencyHistogram the registry histogram exposes, one data structure
        self.latency = self._h_latency.labels().hist
        self._submit_ts: dict[int, float] = {}
        self.host_store: dict[int, dict] = {}    # rid -> evicted pages
        # rid -> raw pages still in HBM, in retirement order (FIFO eviction)
        self.resident_store: "collections.OrderedDict[int, dict]" = \
            collections.OrderedDict()
        self._next_rid = 0
        self._decode = jax.jit(
            lambda p, c, t, pos: decode_step(p, cfg, t, pos, c))

    # -- request intake ------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               eos: int | None = None) -> Request:
        r = Request(rid=self._next_rid, prompt=np.asarray(prompt, np.int32),
                    max_new_tokens=max_new_tokens, eos=eos)
        self._next_rid += 1
        self._submit_ts[r.rid] = time.perf_counter()
        self.queue.put(r)
        return r

    # -- slot management -------------------------------------------------------------
    def _fill_slots(self) -> None:
        for b in range(self.B):
            if self.slots[b] is None and not self.queue.empty():
                r = self.queue.get()
                self._prefill(b, r)
                self.slots[b] = r

    def _prefill(self, slot: int, r: Request) -> None:
        """Run the prompt through the full forward, writing slot ``slot``."""
        S = len(r.prompt)
        assert S < self.s_max, (S, self.s_max)
        toks = jnp.asarray(r.prompt, jnp.int32)[None]
        one_cache = init_cache(self.cfg, 1, self.s_max, dtype=self.dtype)
        x, new_cache, _ = forward(self.params, self.cfg, toks,
                                  cache=one_cache)
        logits = project_logits(self.params, self.cfg, x[:, -1])
        first = int(np.asarray(self.sampler(logits))[0])
        r.out_tokens.append(first)
        self.cache = jax.tree.map(
            lambda c, n: c.at[:, slot].set(n[:, 0]), self.cache, new_cache)
        self.pos[slot] = S
        self._c_prefills.inc()

    def _retire(self, slot: int) -> None:
        r = self.slots[slot]
        if r is not None:
            t0 = self._submit_ts.pop(r.rid, None)
            if t0 is not None:
                self.latency.record(time.perf_counter() - t0)
        if r is not None and self.evict_to_host:
            pages = self._snapshot_slot(slot)
            if self.resident_limit > 0:
                self.resident_store[r.rid] = pages
                while len(self.resident_store) > self.resident_limit:
                    # budget exceeded: spill the OLDEST retired page-set
                    old_rid, old_pages = self.resident_store.popitem(last=False)
                    self._host_evict(old_rid, old_pages)
            else:
                self._host_evict(r.rid, pages)
        self.slots[slot] = None
        self.pos[slot] = 0

    # -- KV eviction (paper Eq. 1/2 at the HBM<->host level) -------------------------
    def _snapshot_slot(self, slot: int) -> dict:
        """Copy one slot's KV pages out of the decode cache (still in HBM)."""
        pages = {}

        def snap_leaf(path, c):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            pages[name] = c[:, slot]
            return c
        jax.tree_util.tree_map_with_path(snap_leaf, self.cache)
        return pages

    def _host_evict(self, rid: int, pages: dict) -> None:
        """BFP8-encode a page-set across the HBM -> host boundary."""
        enc_pages = {}
        for name, page in pages.items():
            page = np.asarray(page, np.float32)
            enc = bfp8_encode(page)
            self._c_evicted_bytes.labels(kind="raw").inc(
                page.size * 2)                                 # bf16 words
            self._c_evicted_bytes.labels(kind="compressed").inc(
                enc.mantissas.size + enc.exponents.size)
            enc_pages[name] = enc
        self.host_store[rid] = enc_pages
        self._c_evicted_pages.inc(len(enc_pages))

    def restore_request(self, rid: int, slot: int) -> None:
        """Bring an evicted request's pages back into HBM (resumption).

        Pages still parked under ``resident_limit`` restore exactly; pages
        that crossed to the host come back through the BFP8 codec.
        """
        resident = self.resident_store.pop(rid, None)

        def page_for(name, c):
            if resident is not None:
                return np.asarray(resident[name])
            return bfp8_decode(self.host_store[rid][name])

        def restore_leaf(path, c):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            page = np.asarray(page_for(name, c)).astype(np.asarray(c).dtype)
            self._c_restored_pages.inc()
            return c.at[:, slot].set(jnp.asarray(page))
        self.cache = jax.tree_util.tree_map_with_path(restore_leaf, self.cache)
        if resident is None:
            del self.host_store[rid]

    # -- decode loop ---------------------------------------------------------------
    def step(self) -> int:
        """One lockstep decode step over all active slots; returns #active."""
        self._fill_slots()
        active = [b for b, s in enumerate(self.slots) if s is not None]
        if not active:
            return 0
        last = np.zeros((self.B, 1), np.int32)
        for b in active:
            last[b, 0] = self.slots[b].out_tokens[-1]
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(last),
            jnp.asarray(self.pos, jnp.int32))
        nxt = np.asarray(self.sampler(logits))
        self._c_decode.inc()
        for b in active:
            r = self.slots[b]
            self.pos[b] += 1
            r.out_tokens.append(int(nxt[b]))
            self._c_generated.inc()
            if (len(r.out_tokens) >= r.max_new_tokens
                    or (r.eos is not None and int(nxt[b]) == r.eos)
                    or self.pos[b] >= self.s_max - 1):
                r.done = True
                self._retire(b)
        return len(active)

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and self.queue.empty():
                return

    def metrics_text(self) -> str:
        """Prometheus text exposition of this engine's registry."""
        return self.metrics.metrics_text()


# =============================================================================
# Batched exec-graph front-end feeding the pipelined streamer
# =============================================================================

class StreamServerStats(_RegistryStats):
    """Live view of the stream server's counters (see ``_RegistryStats``)."""

    _PREFIX = "smof_server_"

    @property
    def frames_in(self) -> int:
        return self._value("smof_server_frames_in_total")

    @property
    def frames_out(self) -> int:
        return self._value("smof_server_frames_out_total")

    @property
    def streams_run(self) -> int:
        return self._value("smof_server_streams_total")

    @property
    def padded_frames(self) -> int:
        # bubble frames added to fill the last stream
        return self._value("smof_server_padded_frames_total")


class GraphStreamServer:
    """Packs submitted frames into microbatch streams for the streamer.

    The pipelined executor is traced for a fixed stream length ``B``
    (`runtime/streamer`): this front-end queues individual frames, cuts the
    queue into length-``B`` streams (zero-padding the tail — padding frames
    are executed as pipeline bubbles and dropped), runs each stream through
    the one jitted multi-microbatch step, and hands results back by ticket.

    Construction goes through the compile façade (``repro.api``): pass a
    ready :class:`~repro.api.CompileSpec` (``spec=``), an already-lowered
    ``StreamingExecutor`` (``executor=``, what ``Compiled.serve()`` does),
    or the legacy ``(g, plan, microbatches=..., **lowering knobs)`` form —
    which is folded into a spec, so the lowering-kwarg plumbing lives in
    exactly one place.
    """

    def __init__(self, g=None, plan=None, *, microbatches: int = 8,
                 executor=None, spec=None, metrics: MetricsRegistry | None = None,
                 slo=None, resident_limit: int = 0, **lower_kw):
        from repro.api import CompileSpec, compile as smof_compile
        if executor is None:
            if spec is None:
                spec = CompileSpec(model=g, strategy="manual-plan",
                                   mode="pipelined", plan=plan,
                                   microbatches=microbatches, **lower_kw)
            executor = smof_compile(spec).executor
        self.executor = executor
        self.microbatches = executor.microbatches
        # flushed-but-unclaimed results allowed to stay resident (live
        # arrays) before the oldest is evicted to the byte-packed host
        # store; 0 = unbounded.  Restoration is exact — results are
        # finished outputs, so unlike the KV pages there is nothing to
        # re-quantise and the eviction must be lossless.
        self.resident_limit = resident_limit
        # registry-backed accounting (own registry by default; pass one to
        # share a scrape surface, e.g. Compiled.serve threads the artifact's)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self._c_frames_in = m.counter(
            "smof_server_frames_in_total", "frames submitted to the server")
        self._c_frames_out = m.counter(
            "smof_server_frames_out_total", "frames delivered by flush")
        self._c_streams = m.counter(
            "smof_server_streams_total",
            "fixed-length microbatch streams executed")
        self._c_padded = m.counter(
            "smof_server_padded_frames_total",
            "bubble frames padded onto stream tails")
        self._h_latency = m.histogram(
            "smof_server_frame_latency_seconds",
            "submit -> flush-delivery wall clock per frame")
        self._c_slo = m.counter(
            "smof_server_slo_evaluations_total",
            "per-flush SLO evaluations, by verdict", ("verdict",))
        self.stats = StreamServerStats(m)
        # submit -> flush-delivery wall clock per frame (log-bucketed):
        # queueing delay + padding bubbles + the stream's pipeline run; the
        # same LatencyHistogram the registry histogram exposes
        self.latency = self._h_latency.labels().hist
        self.slo = slo                       # obs.slo.SloEvaluator | None
        self.flight = None                   # obs.flight.FlightRecorder | None
        # per stream executed, every spill record moves offchip_bits once
        # per microbatch in each direction (evict + restore) — the window
        # samples the SLO's spill-bandwidth objectives score, split by
        # direction so one-sided saturation stays visible
        _one_way = sum(
            r.offchip_bits // 8
            for r in getattr(executor.report, "spills", ())
        ) * self.microbatches
        self._evict_bytes_per_stream = _one_way
        self._restore_bytes_per_stream = _one_way
        self._spill_bytes_per_stream = _one_way * 2
        self._c_evicted_results = m.counter(
            "smof_server_evicted_results_total",
            "flushed results spilled to the host store (resident_limit)")
        self._c_restored_results = m.counter(
            "smof_server_restored_results_total",
            "evicted results restored on claim (exact, byte-packed)")
        self.autotune_result = None          # set by .autotuned()
        self._pending: list[tuple[int, np.ndarray]] = []
        # ticket -> output, oldest-flushed first (the eviction order)
        self._results: "collections.OrderedDict[int, np.ndarray]" = \
            collections.OrderedDict()
        # ticket -> (raw bytes, dtype, shape): the off-chip side of the
        # resident budget — exact restore by construction
        self._host_results: dict[int, tuple[bytes, np.dtype, tuple]] = {}
        self._submit_ts: dict[int, float] = {}
        self._next_ticket = 0

    @classmethod
    def autotuned(cls, g, dev, *, autotune_cfg=None, **lower_kw
                  ) -> "GraphStreamServer":
        """Serve the *measured-best* plan instead of the default DSE plan.

        Compiles ``strategy="autotune"`` through the façade: the closed
        loop (``repro.optim.autotune``) executes every candidate through
        the pipelined streamer, and the server is built around the winning
        plan at the autotuner's microbatch depth.  The full
        :class:`~repro.optim.autotune.AutotuneResult` (trajectory +
        calibration report) is kept on ``server.autotune_result``.
        """
        from repro.api import CompileSpec, compile as smof_compile
        from repro.optim.autotune import AutotuneConfig
        cfg = autotune_cfg or AutotuneConfig()
        compiled = smof_compile(CompileSpec(
            model=g, device=dev, strategy="autotune", mode="pipelined",
            autotune_cfg=cfg, microbatches=cfg.microbatches, **lower_kw))
        return compiled.serve()

    @property
    def report(self):
        return self.executor.report

    def submit(self, frame: np.ndarray) -> int:
        """Queue one (positions, channels) frame; returns a ticket id."""
        self._pending.append((self._next_ticket,
                              np.asarray(frame, np.float32)))
        self._submit_ts[self._next_ticket] = time.perf_counter()
        self._next_ticket += 1
        self._c_frames_in.inc()
        return self._next_ticket - 1

    def flush(self) -> dict[int, np.ndarray]:
        """Run all queued frames; returns {ticket: output} for this flush.

        With an attached SLO evaluator (:meth:`enable_slo`), every stream
        run lands one window observation and is re-scored — breaches fire
        the evaluator's ``on_breach`` hooks (e.g. a flight-recorder dump)
        and the verdict counts into ``smof_server_slo_evaluations_total``.

        Each chunk is five host spans in turn (``obs.trace.host_span``):
        ``smof.flush.stack`` (frames stacked and padded to ``B``),
        ``.h2d`` (the copy to the device), ``.run`` (the step's dispatch),
        ``.d2h`` (the wait for the step and the copy back) and ``.claim``
        (outputs handed to their tickets).
        """
        out: dict[int, np.ndarray] = {}
        B = self.microbatches
        while self._pending:
            chunk, self._pending = self._pending[:B], self._pending[B:]
            with host_span("flush.stack"):
                xs = np.stack([f for _, f in chunk])
                pad = B - len(chunk)
                if pad:
                    xs = np.concatenate(
                        [xs, np.zeros((pad,) + xs.shape[1:], np.float32)])
                    self._c_padded.inc(pad)
            t_run = time.perf_counter()
            with host_span("flush.h2d"):
                xs_dev = jnp.asarray(xs)
            with host_span("flush.run"):
                ys_dev = self.executor(xs_dev)
            with host_span("flush.d2h"):
                ys = np.asarray(ys_dev)
            run_s = time.perf_counter() - t_run
            self._c_streams.inc()
            now = time.perf_counter()
            with host_span("flush.claim"):
                for (ticket, _), y in zip(chunk, ys):
                    out[ticket] = y
                    self._c_frames_out.inc()
                    t0 = self._submit_ts.pop(ticket, None)
                    if t0 is not None:
                        self.latency.record(now - t0)
            if self.slo is not None:
                self.slo.observe(frames=len(chunk), seconds=run_s,
                                 spill_bytes=self._spill_bytes_per_stream,
                                 evict_bytes=self._evict_bytes_per_stream,
                                 restore_bytes=self._restore_bytes_per_stream)
                verdict = self.slo.evaluate().verdict
                self._c_slo.labels(verdict=verdict).inc()
        self._results.update(out)
        if self.resident_limit > 0:
            while len(self._results) > self.resident_limit:
                # budget exceeded: spill the OLDEST unclaimed result —
                # same retirement-order policy as the decode engine's
                # KV pages, but lossless (finished outputs)
                ticket, y = self._results.popitem(last=False)
                self._host_results[ticket] = (y.tobytes(), y.dtype, y.shape)
                self._c_evicted_results.inc()
        return out

    # -- observability surface ------------------------------------------------
    def metrics_text(self) -> str:
        """Prometheus text exposition of this server's registry."""
        return self.metrics.metrics_text()

    def roofline_fps(self) -> float | None:
        """The served plan's Eq. 6 throughput bound in frames/s, when the
        plan's provenance carries a calibrated ``s_per_cycle`` (autotuned
        artifacts do): ``1 / (eq6_cycles * s_per_cycle)``."""
        plan = getattr(self.executor, "plan", None)
        spc = plan.provenance.get("s_per_cycle") if plan is not None else None
        eq6 = getattr(self.executor.report, "eq6_time", None)
        if spc and eq6:
            return 1.0 / (eq6 * spc)
        return None

    def enable_slo(self, cfg=None, *, roofline_fps=None, bw_gbps=None,
                   stream_budgets=None):
        """Attach a rolling-window SLO evaluator, re-scored on every flush.

        ``roofline_fps`` defaults to :meth:`roofline_fps` (calibrated
        plans only); ``bw_gbps`` is the device's off-chip budget for the
        spill-bandwidth objective.  ``stream_budgets`` (per-kind Gbps,
        e.g. ``MemoryModel.budget_gbps_by_kind()``) scores the split
        evict/restore objectives against the arbiter's grants; defaults
        to the executor report's channel model when the plan was compiled
        with one.  Returns the evaluator so callers can hook
        ``on_breach`` (e.g. ``FlightRecorder.on_slo_report``).
        """
        from repro.obs.slo import SloEvaluator
        if roofline_fps is None:
            roofline_fps = self.roofline_fps()
        if stream_budgets is None:
            mem = getattr(self.executor.report, "memory", None)
            if mem is not None:
                stream_budgets = mem.budget_gbps_by_kind()
        self.slo = SloEvaluator(cfg, roofline_fps=roofline_fps,
                                bw_gbps=bw_gbps, latency=self.latency,
                                stream_budgets=stream_budgets)
        return self.slo

    def result(self, ticket: int) -> np.ndarray:
        """Claim a flushed output (one-shot: the server does not keep
        delivered results, so a long-lived front-end stays bounded).

        Results evicted under ``resident_limit`` restore bit-exactly from
        the host byte store."""
        if ticket in self._host_results:
            raw, dtype, shape = self._host_results.pop(ticket)
            self._c_restored_results.inc()
            return np.frombuffer(raw, dtype=dtype).reshape(shape)
        return self._results.pop(ticket)
