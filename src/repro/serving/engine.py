"""Batched serving front-end for the streaming executor.

``GraphStreamServer`` packs submitted frames into fixed-length microbatch
streams and runs them through the pipelined streaming executor
(``runtime/streamer``).  ``GraphStreamServer.autotuned`` runs the
closed-loop autotuner (``repro.optim.autotune``) first and serves the
measured-best plan.
"""
from __future__ import annotations

import collections
import time

import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import host_span


class _RegistryStats:
    """Base for the registry-backed stats views.

    The :class:`~repro.obs.metrics.MetricsRegistry` is the single source
    of truth and these views are *live reads* of it — the attribute
    surface (``stats.frames_in`` etc.) maps each field to its metric
    sample, and ``report()`` is the registry snapshot filtered to this
    front-end's namespace.
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
        # finished outputs, so the eviction must be lossless.
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
                # budget exceeded: spill the OLDEST unclaimed result,
                # losslessly (finished outputs)
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
