"""The one SMOF compile façade: ``CompileSpec`` -> ``Compiled`` artifact.

SMOF's pitch is a *toolflow*: one entry point takes a CNN graph plus a
device and emits a deployable streaming design with off-chip eviction
decisions baked in.  This module is that entry point for the whole repo —
the single seam where model resolution (``core.builders.get_model``),
plan search (``core.dse.run_dse`` / ``optim.autotune``), lowering
(``runtime.executor.lower_plan`` / ``runtime.streamer
.lower_plan_pipelined``), serving (``serving.engine.GraphStreamServer``)
and artifact persistence meet.  The low-level functions stay public, but
every driver in this repo (benchmarks, examples, serving, the autotune
CLI) goes through here:

    import repro

    compiled = repro.compile(repro.CompileSpec(
        model="unet_exec", device="u200", mode="pipelined"))
    y = compiled.run(x)                    # execute one frame / stream
    print(compiled.report())               # unified traffic + schedule view
    compiled.save("unet.smof.json")        # versioned plan artifact
    srv = compiled.serve()                 # batched streaming front-end

    again = repro.Compiled.load("unet.smof.json")   # fresh process OK:
    again.run(x)                           # bit-identical (seeded params)

Spec knobs -> subsystems
------------------------
``strategy``  "dse" (Algorithm 1, the default), "autotune" (closed-loop
              measured search, ``optim/autotune.py``), or "manual-plan"
              (caller supplies ``spec.plan``).
``mode``      "reference" (dense baseline, no plan), "staged" (sequential
              executor, the Eq. 5 regime), "pipelined" (1F1B streamer,
              the Eq. 6 regime).
``kernel_mode`` / ``use_pallas`` / ``interpret``
              kernel dispatch policy (``use_pallas`` is the boolean
              shorthand: True -> "pallas", False -> "reference").
``microbatches`` stream depth B the pipelined executor is traced for
              (an ``autotune_cfg`` overrides it with the depth the search
              measured at).
``dse`` / ``autotune_cfg`` / ``seed``
              search configuration; ``seed`` also fixes the deterministic
              per-vertex weights, which is what makes saved artifacts
              reproduce bit-identically in a fresh process.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
from typing import Any

from .core.builders import (EXEC_MODELS, PAPER_MODELS, exec_input_shape,
                            get_model)
from .core.dse import DSEConfig, run_dse
from .core.graph import Graph
from .core.plan import ExecutionPlan, PLAN_SCHEMA_VERSION, plan_from_dse
from .core.resources import ALL_DEVICES, Device, get_device
from .memory import POLICIES, ChannelConfig
from .obs.metrics import MetricsRegistry
from .obs.trace import NULL_RECORDER, ObsConfig, TraceRecorder, host_span

MODES = ("reference", "staged", "pipelined")
STRATEGIES = ("dse", "autotune", "manual-plan")

ARTIFACT_KIND = "smof-compiled"
ARTIFACT_SCHEMA_VERSION = 1

# The default executable-path DSE configuration: eviction + fragmentation
# friendly settings at 16-bit stream words (matches the autotuner's seed).
_DEFAULT_DSE = DSEConfig(batch=1, codecs=("none", "bfp8"), word_bits=16,
                         cut_kinds=("pool", "conv"))


@dataclasses.dataclass
class CompileSpec:
    """Everything the toolflow needs to go graph + device -> executable.

    ``model`` is a registry name (``EXEC_MODELS`` / ``PAPER_MODELS``) or an
    already-built :class:`~repro.core.graph.Graph`; ``device`` a registry
    name (``ALL_DEVICES``) or a :class:`~repro.core.resources.Device`.
    """
    model: str | Graph
    device: str | Device = "u200"
    strategy: str = "dse"              # dse | autotune | manual-plan
    mode: str = "staged"               # reference | staged | pipelined
    kernel_mode: str = "auto"          # auto | pallas | reference
    microbatches: int = 8              # pipelined stream depth B
    use_pallas: bool | None = None     # bool shorthand over kernel_mode
    autotune_cfg: Any = None           # optim.autotune.AutotuneConfig
    seed: int = 0                      # weight init + search RNG
    plan: ExecutionPlan | None = None  # strategy="manual-plan" input
    dse: DSEConfig | None = None       # strategy="dse" knobs
    interpret: bool | None = None      # Pallas interpret-mode override
    placement: str = "interleave"      # pipelined: interleave | shard_map
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    #: opt-in off-chip channel model (``repro.memory``): arbitration
    #: policy + optional gbps override; pipelined lowerings then carry the
    #: contended Eq. 5/6 bounds and prefetch deadline accounting.
    channel: ChannelConfig | None = None

    def resolved_kernel_mode(self) -> str:
        if self.use_pallas is None:
            return self.kernel_mode
        return "pallas" if self.use_pallas else "reference"

    def validate(self) -> None:
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r}; pick one of "
                             f"{MODES}")
        if self.strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}; pick one "
                             f"of {STRATEGIES}")
        if (self.strategy == "manual-plan" and self.plan is None
                and self.mode != "reference"):
            raise ValueError('strategy="manual-plan" needs spec.plan '
                             '(mode="reference" is the plan-free baseline)')
        if self.microbatches < 1:
            raise ValueError(f"need >= 1 microbatch, got {self.microbatches}")


def _resolve_graph(spec: CompileSpec) -> Graph:
    if isinstance(spec.model, Graph):
        return spec.model
    return get_model(spec.model)()


def _resolve_device(spec: CompileSpec) -> Device:
    if isinstance(spec.device, Device):
        return spec.device
    return get_device(spec.device)


def _device_name(spec: CompileSpec, plan: ExecutionPlan | None) -> str:
    if isinstance(spec.device, Device):
        return spec.device.name
    if spec.strategy == "manual-plan" and plan is not None and plan.device:
        return plan.device          # the artifact's own record wins
    return spec.device


def _autotune_digest(result) -> str:
    """Stable short digest of the search trajectory (provenance stamp)."""
    payload = json.dumps(result.trajectory_rows(), sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def build_plan(spec: CompileSpec, graph: Graph | None = None, *,
               metrics: MetricsRegistry | None = None
               ) -> tuple[ExecutionPlan | None, Any]:
    """Resolve the spec's decision vector: ``(plan, autotune_result)``.

    This is the search half of :func:`compile` — usable on its own for
    paper-scale (cost-model-only) graphs that cannot be lowered.  Returns
    ``(None, None)`` for ``mode="reference"`` (the dense baseline ignores
    any plan) and ``autotune_result=None`` unless ``strategy="autotune"``.

    The returned plan carries provenance: strategy, device name, and — for
    autotuned plans — the calibration ``s_per_cycle`` plus a digest of the
    measured trajectory.
    """
    spec.validate()
    g = graph if graph is not None else _resolve_graph(spec)
    if spec.mode == "reference":
        return None, None

    autotune_result = None
    cfg = None
    if spec.strategy == "manual-plan":
        plan = spec.plan
        if plan is not None:
            plan.validate()       # typed PlanValidationError, not a crash
                                  # deep inside the lowering
    elif spec.strategy == "autotune":
        from .optim.autotune import AutotuneConfig, autotune
        cfg = spec.autotune_cfg or AutotuneConfig(
            microbatches=spec.microbatches,
            kernel_mode=spec.resolved_kernel_mode(), seed=spec.seed)
        rec = TraceRecorder() if spec.obs.enabled else NULL_RECORDER
        autotune_result = autotune(g, _resolve_device(spec), cfg,
                                   recorder=rec, metrics=metrics)
        plan = autotune_result.best_plan
    else:                                     # "dse": Algorithm 1
        dev = _resolve_device(spec)
        res = run_dse(g, dev, spec.dse or _DEFAULT_DSE)
        plan = plan_from_dse(g.name, dev.name, res,
                             microbatch=spec.microbatches)

    prov = {"compiled_by": "repro.api.compile",
            "strategy": spec.strategy,
            "device": _device_name(spec, plan),
            "seed": spec.seed}
    if autotune_result is not None:
        prov.update({
            "s_per_cycle": autotune_result.calibration.s_per_cycle,
            "autotune_digest": _autotune_digest(autotune_result),
            "autotune_candidates": len(autotune_result.trajectory),
            # the search's own knobs — a caller-supplied cfg may differ
            # from the spec's, and provenance records what actually ran
            "autotune_seed": cfg.seed,
            "autotune_kernel_mode": cfg.kernel_mode,
            "baseline_fps": autotune_result.baseline_fps,
            "best_fps": autotune_result.best_fps,
        })
    for k, v in prov.items():
        plan.provenance.setdefault(k, v)
    return plan, autotune_result


def compile(spec: CompileSpec) -> "Compiled":
    """The toolflow entry point: resolve, search, lower — one call.

    Resolves the graph through the model registry, produces an
    :class:`~repro.core.plan.ExecutionPlan` per ``spec.strategy``, lowers
    it per ``spec.mode``, and returns a :class:`Compiled` artifact that can
    run, serve, report, and persist itself.  Numerics are bit-identical to
    calling the underlying ``lower_plan`` / ``lower_plan_pipelined``
    directly with the same plan and seed.

    The search and the lowering are the host spans ``smof.compile.search``
    and ``smof.compile.lower``; XLA compiles the step at its first call.
    """
    spec.validate()
    g = _resolve_graph(spec)
    # one registry per artifact: the autotune search, traced runs and any
    # server built from this compile all land on the same scrape surface
    registry = MetricsRegistry()
    with host_span("compile.search"):
        plan, autotune_result = build_plan(spec, g, metrics=registry)
    km = spec.resolved_kernel_mode()

    with host_span("compile.lower"):
        if spec.mode == "reference":
            from .runtime.executor import reference_pipeline
            executor = reference_pipeline(g, seed=spec.seed)
        elif spec.mode == "staged":
            from .runtime.executor import lower_plan
            executor = lower_plan(g, plan, kernel_mode=km, seed=spec.seed,
                                  interpret=spec.interpret)
        else:                                 # "pipelined"
            from .runtime.streamer import lower_plan_pipelined
            B = spec.microbatches
            if autotune_result is not None:   # serve at the measured depth
                B = autotune_result.microbatches
            try:
                dev = _resolve_device(spec)
            except (KeyError, ValueError):
                dev = None
            executor = lower_plan_pipelined(
                g, plan, microbatches=B, kernel_mode=km, seed=spec.seed,
                interpret=spec.interpret, placement=spec.placement,
                channel=spec.channel, device=dev)

    return Compiled(spec=spec, graph=g, device=_device_name(spec, plan),
                    plan=plan, executor=executor,
                    autotune_result=autotune_result, registry=registry)


@dataclasses.dataclass
class Compiled:
    """A deployable compiled design: executor + plan + provenance.

    ``run(x)`` executes (staged/reference: one frame -> ``(L,)``;
    pipelined: a ``(B,) + frame`` stream -> ``(B, L)``, or a single frame,
    broadcast through the pipeline, -> ``(L,)``); a frame is
    :meth:`input_shape`.  ``serve()`` wraps the pipelined executor in a
    :class:`GraphStreamServer`;
    ``report()`` unifies the Spill/Stream/Calibration reports; ``save`` /
    ``load`` round-trip a versioned plan artifact that reproduces
    bit-identically in a fresh process (weights are seeded).
    """
    spec: CompileSpec
    graph: Graph
    device: str
    plan: ExecutionPlan | None
    executor: Any                    # LoweredPipeline | StreamingExecutor
    autotune_result: Any = None      # optim.autotune.AutotuneResult
    model_check: Any = None          # obs.ModelCheck, set by trace()
    recorder: Any = None             # obs.TraceRecorder, set by trace()
    # one scrape surface per artifact: trace() and serve() both feed it
    registry: MetricsRegistry = dataclasses.field(
        default_factory=MetricsRegistry)

    @property
    def model(self) -> str:
        return self.graph.name

    @property
    def mode(self) -> str:
        return self.spec.mode

    @property
    def strategy(self) -> str:
        """Where the plan's decisions came from.  Reads the plan's own
        provenance when present, so a loaded artifact (whose spec strategy
        is necessarily "manual-plan" — decisions are baked in) still
        reports and re-saves the strategy that produced it."""
        if self.plan is not None and "strategy" in self.plan.provenance:
            return self.plan.provenance["strategy"]
        return self.spec.strategy

    def __call__(self, x):
        return self.run(x)

    def run(self, x):
        """Dispatch one call of the executor; the host span ``smof.run``
        covers the dispatch, not the device's work."""
        import jax.numpy as jnp
        with host_span("run"):
            x = jnp.asarray(x)
            if (self.mode == "pipelined"
                    and x.ndim == len(self.input_shape())):
                # single-frame convenience: broadcast through the stream,
                # every slot computes the same frame — return one output
                B = self.executor.microbatches
                return self.executor(jnp.broadcast_to(x, (B,) + x.shape))[0]
            return self.executor(x)

    def input_shape(self) -> tuple[int, ...]:
        """One frame's shape: ``(m, c)``, or ``(H, W, c)``."""
        return exec_input_shape(self.graph)

    # -- unified reporting ----------------------------------------------------
    def report(self) -> dict:
        """One dict over all report families the toolflow produced:
        SpillReport (staged) / StreamReport (pipelined) summaries under
        ``traffic``, plan provenance, and — when the autotuner ran — its
        summary incl. the CalibrationReport.  A spatial graph adds
        ``line_buffers``: per k x k conv, the kernel's layout (taps per
        dot, dot depth), row blocks and the halo it reads again, beside
        the DSE's Eq. 1 depth
        (``runtime.executor.line_buffers``)."""
        out = {
            "model": self.model,
            "device": self.device,
            "mode": self.mode,
            "strategy": self.strategy,
            "kernel_mode": self.spec.resolved_kernel_mode(),
            "schema_version": (self.plan.schema_version if self.plan
                               else PLAN_SCHEMA_VERSION),
            "n_stages": self.plan.n_stages if self.plan else 1,
            "traffic": self.executor.report.summary(),
        }
        if self.plan is not None:
            out["provenance"] = dict(self.plan.provenance)
        if self.autotune_result is not None:
            out["autotune"] = self.autotune_result.summary()
        if self.model_check is not None:
            out["model_check"] = self.model_check.summary()
        from .runtime.executor import line_buffers, resolve_kernel_mode
        use_pallas = self.mode != "reference" and resolve_kernel_mode(
            self.spec.resolved_kernel_mode(), self.spec.interpret)[0]
        lb = line_buffers(self.graph, use_pallas=use_pallas)
        if lb:
            out["line_buffers"] = lb
        return out

    def metrics(self) -> dict:
        """The artifact's metrics snapshot (``{sample_key: value}``).

        Every traced run (:meth:`trace`) and every server built by
        :meth:`serve` feeds the artifact's one
        :class:`~repro.obs.metrics.MetricsRegistry`, so this is the whole
        design's scrape surface; :meth:`metrics_text` is the Prometheus
        exposition of the same registry.
        """
        return self.registry.snapshot()

    def metrics_text(self) -> str:
        """Prometheus text exposition of :meth:`metrics`."""
        return self.registry.metrics_text()

    # -- tracing --------------------------------------------------------------
    def trace(self, x=None, *, path=None, recorder=None):
        """Execute once with telemetry on; returns ``(outputs, ModelCheck)``.

        Pipelined designs run tick-by-tick through
        ``StreamingExecutor.run_traced`` — per-tick wall-clock spans, queue
        counters, spill bytes — and yield a full
        :class:`~repro.obs.ModelCheck` (measured vs Eq. 5/6 latencies,
        Eq. 1 queue bounds), which subsequent :meth:`report` calls include.
        Staged/reference designs record one frame span plus spill counters
        and yield ``model_check=None``.

        ``x=None`` synthesizes a seeded input stream; ``path`` (default:
        ``spec.obs.trace_path``) writes the Chrome trace-event JSON —
        open it in Perfetto / ``chrome://tracing``.

        With ``spec.obs.flight_capacity > 0`` the default recorder is a
        bounded :class:`~repro.obs.flight.FlightRecorder` ring instead,
        which auto-dumps to ``spec.obs.flight_path`` if the run's
        ModelCheck comes back violated.
        """
        import jax.numpy as jnp
        import numpy as np

        if recorder is not None:
            rec = recorder
        elif self.spec.obs.flight_capacity > 0:
            from .obs.flight import FlightRecorder
            rec = FlightRecorder(self.spec.obs.flight_capacity,
                                 path=self.spec.obs.flight_path)
        else:
            rec = TraceRecorder()
        shape = self.input_shape()
        if x is None:
            rng = np.random.default_rng(self.spec.seed)
            x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        else:
            x = jnp.asarray(x)
        mc = None
        if self.mode == "pipelined":
            if x.ndim == len(shape):
                B = self.executor.microbatches
                x = jnp.broadcast_to(x, (B,) + x.shape)
            y, mc = self.executor.run_traced(x, rec, metrics=self.registry)
        else:
            y = self.executor.run_traced(x, rec)
        self.model_check = mc
        self.recorder = rec
        if (mc is not None and getattr(rec, "path", None) is not None
                and hasattr(rec, "on_model_check")):
            rec.on_model_check(mc)       # flight ring: dump on violation
        path = path if path is not None else self.spec.obs.trace_path
        if path is not None and rec.enabled:
            rec.save(path)
        return y, mc

    # -- serving --------------------------------------------------------------
    def serve(self, *, resident_limit: int = 0, **kw):
        """Batched streaming front-end around this design.

        Reuses the pipelined executor when this artifact is already
        pipelined and no overrides are given; otherwise re-lowers the same
        plan pipelined with ``kw`` applied as :class:`CompileSpec`
        overrides (e.g. ``microbatches=16``).  Unless overridden, the
        stream depth follows the current executor's (so an autotuned
        artifact keeps serving at the depth the search measured at).
        ``resident_limit`` bounds the flushed-but-unclaimed results kept
        resident by the server (oldest spill to an exact host byte store).

        The server shares this artifact's metrics registry (one scrape
        surface, read via :meth:`metrics` / ``server.metrics_text()``).
        When ``spec.obs.slo`` is set, a rolling-window
        :class:`~repro.obs.slo.SloEvaluator` is attached — roofline from
        the plan's calibrated provenance, spill bandwidth budget from the
        device sheet — and, with ``spec.obs.flight_capacity > 0``, an SLO
        breach dumps a :class:`~repro.obs.flight.FlightRecorder` ring to
        ``spec.obs.flight_path``."""
        from .serving.engine import GraphStreamServer
        if self.mode != "pipelined" and self.plan is None:
            raise ValueError(
                'mode="reference" compiles are plan-free and cannot be '
                'served; compile with mode="staged"/"pipelined" (any '
                "strategy) to get a servable plan")
        if self.mode == "pipelined" and not kw:
            sx = self.executor
        else:
            kw.setdefault("microbatches",
                          getattr(self.executor, "microbatches",
                                  self.spec.microbatches))
            sx = compile(dataclasses.replace(
                self.spec, mode="pipelined", strategy="manual-plan",
                plan=self.plan, **kw)).executor
        srv = GraphStreamServer(executor=sx, metrics=self.registry,
                                resident_limit=resident_limit)
        srv.autotune_result = self.autotune_result
        if self.spec.obs.slo is not None:
            try:
                bw = _resolve_device(self.spec).offchip_gbps
            except (KeyError, ValueError):
                bw = None
            evaluator = srv.enable_slo(self.spec.obs.slo, bw_gbps=bw)
            if (self.spec.obs.flight_capacity > 0
                    and self.spec.obs.flight_path is not None):
                from .obs.flight import FlightRecorder
                flight = FlightRecorder(self.spec.obs.flight_capacity,
                                        path=self.spec.obs.flight_path)
                evaluator.on_breach.append(flight.on_slo_report)
                srv.flight = flight
        return srv

    # -- persistence ----------------------------------------------------------
    def save(self, path) -> pathlib.Path:
        """Write the versioned compile artifact (JSON): the plan with its
        provenance, the graph structure (so custom-built graphs reload
        exactly, without the model registry), plus every spec knob ``load``
        needs to re-lower it."""
        path = pathlib.Path(path)
        B = (self.executor.microbatches if self.mode == "pipelined"
             else self.spec.microbatches)
        payload = {
            "artifact": ARTIFACT_KIND,
            "artifact_schema_version": ARTIFACT_SCHEMA_VERSION,
            "plan_schema_version": (self.plan.schema_version if self.plan
                                    else PLAN_SCHEMA_VERSION),
            "model": self.model,
            "device": self.device,
            "mode": self.mode,
            "strategy": self.strategy,   # decision origin: save/load-stable
            "kernel_mode": self.spec.resolved_kernel_mode(),
            # the resolved Pallas interpret override (None = decide per
            # backend via kernels.ops.resolve_interpret): saved so the
            # artifact replays with the kernel path it was compiled with
            "interpret": self.spec.interpret,
            "microbatches": B,
            "seed": self.spec.seed,
            "placement": self.spec.placement,
            "obs": self.spec.obs.to_dict(),
            "channel": (self.spec.channel.to_dict()
                        if self.spec.channel is not None else None),
            "graph": self.graph.to_json_dict(),
            "plan": (json.loads(self.plan.to_json())
                     if self.plan is not None else None),
        }
        path.write_text(json.dumps(payload, indent=1))
        return path

    @staticmethod
    def load(path) -> "Compiled":
        """Reconstruct a saved artifact and re-lower it.

        The artifact bakes the searched decisions in, so loading never
        re-runs DSE or the autotuner (``strategy`` becomes "manual-plan")
        and rebuilds the graph from the embedded structural dump; with the
        stored seed the reconstructed executor is bit-identical — including
        in a fresh process."""
        d = json.loads(pathlib.Path(path).read_text())
        if d.get("artifact") != ARTIFACT_KIND:
            raise ValueError(f"{path}: not a {ARTIFACT_KIND} artifact")
        if d.get("artifact_schema_version", 0) > ARTIFACT_SCHEMA_VERSION:
            raise ValueError(
                f"{path}: artifact schema v{d['artifact_schema_version']} is "
                f"newer than this toolflow (v{ARTIFACT_SCHEMA_VERSION})")
        plan = (ExecutionPlan.from_json(json.dumps(d["plan"]))
                if d.get("plan") is not None else None)
        model = (Graph.from_json_dict(d["graph"]) if d.get("graph")
                 else d["model"])
        placement = d.get("placement", "interleave")
        if placement == "auto":     # older artifacts: the one-device scan
            placement = "interleave"
        spec = CompileSpec(
            model=model, device=d["device"], strategy="manual-plan",
            mode=d["mode"], kernel_mode=d["kernel_mode"],
            microbatches=d["microbatches"], seed=d["seed"],
            interpret=d.get("interpret"), placement=placement, plan=plan,
            obs=ObsConfig.from_dict(d.get("obs", {})),
            channel=(ChannelConfig.from_dict(d["channel"])
                     if d.get("channel") else None))
        return compile(spec)


# =============================================================================
# Shared CLI surface (examples / benchmark / autotune entry points)
# =============================================================================

def add_compile_args(ap, *, default_model: str | None = "unet_exec",
                     default_device: str = "u200",
                     default_mode: str = "staged",
                     models: dict | None = None,
                     modes: tuple[str, ...] = MODES):
    """Attach the canonical ``--model/--device/--mode`` flags to ``ap``.

    Choices come from the registries (``EXEC_MODELS`` + ``PAPER_MODELS``
    by default, or the narrower ``models`` dict), never from hand-kept
    lists — a new registered builder is immediately reachable from every
    CLI that uses this helper.  ``modes`` narrows the ``--mode`` choices
    for CLIs where some modes make no sense (e.g. the plan-free
    "reference" mode in the autotune CLI)."""
    names = sorted(models if models is not None
                   else {**EXEC_MODELS, **PAPER_MODELS})
    ap.add_argument("--model", default=default_model, choices=names,
                    help=f"model registry name (default: {default_model})")
    ap.add_argument("--device", default=default_device,
                    choices=sorted(ALL_DEVICES),
                    help=f"device registry name (default: {default_device})")
    ap.add_argument("--mode", default=default_mode, choices=list(modes),
                    help=f"execution mode (default: {default_mode})")
    ap.add_argument("--kernel-mode", default="auto",
                    choices=("auto", "pallas", "reference"),
                    help="kernel dispatch: pallas = streaming_conv bodies "
                         "with the fused BFP8 boundary codec (interpret "
                         "mode off TPU), reference = pure-jnp oracles, "
                         "auto = pallas on TPU only (default)")
    ap.add_argument("--channel", default=None, choices=list(POLICIES),
                    help="model the shared off-chip channel with this "
                         "arbitration policy (default: off)")
    ap.add_argument("--channel-gbps", default=None, type=float,
                    help="override the device's off-chip bandwidth for "
                         "the channel model (implies --channel "
                         "round-robin when --channel is not given)")
    return ap


def spec_from_args(args, **overrides) -> CompileSpec:
    """Build a :class:`CompileSpec` from ``add_compile_args`` output."""
    kw: dict[str, Any] = {"model": args.model, "device": args.device,
                          "mode": args.mode}
    if getattr(args, "kernel_mode", None) is not None:
        kw["kernel_mode"] = args.kernel_mode
    policy = getattr(args, "channel", None)
    gbps = getattr(args, "channel_gbps", None)
    if policy is not None or gbps is not None:
        kw["channel"] = ChannelConfig(policy=policy or "round-robin",
                                      gbps=gbps)
    kw.update(overrides)
    return CompileSpec(**kw)
