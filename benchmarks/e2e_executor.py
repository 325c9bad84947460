"""§E2E — execute a DSE-chosen plan as a real JAX pipeline.

Closes the loop the analytical benchmarks leave open: Algorithm 1 picks an
eviction/fragmentation plan for a skip-connection-heavy graph on a
memory-limited device view, the runtime lowers it, and we report *executed*
throughput next to the Eq. 5/6 estimates — for both executors:

* ``sequential`` — ``runtime/executor.lower_plan``: one frame at a time,
  stages back to back (the Eq. 5 regime);
* ``pipelined``  — ``runtime/streamer.lower_plan_pipelined``: stages
  overlap over a stream of microbatches, spills double-buffered (the Eq. 6
  regime).  Enabled with ``--pipelined``.

Both land in one artifact with a shared row schema (CSV on stdout via
``common.emit``; JSON rows with ``--json PATH``):

  executor        "sequential" | "pipelined"
  model, codecs   workload + allowed eviction codecs
  n_stages        stages in the DSE plan
  microbatches    stream length B (1 for sequential)
  fps_executed    measured frames/s (steady state, best of N)
  fps_eq5         1 / sum_j(L_j)   — sequential-schedule estimate
  fps_eq6         1 / max_j(L_j)   — pipelined-schedule estimate
  rel_err         max relative deviation vs the dense reference
  offchip_kbits   per-frame off-chip spill traffic (Spill/StreamReport)
  channel_policy  off-chip arbitration policy of the pipelined compile
                  ("none" when no channel model is attached)
  fps_contended_eq6
                  fps_eq6 scaled by the contended-Eq.6 slowdown of the
                  ``repro.memory`` channel model (== fps_eq6 when the
                  channel is uncontended or absent; 0 when a stream is
                  starved outright)
  prefetch_deadline_misses
                  weight-prefetch slots that miss their stage-start
                  deadline under the arbitrated bandwidth

``L_j`` are per-stage wall-clock latencies measured stage-by-stage
(``streamer.measured_stage_latencies``) so fps_eq5/fps_eq6 bracket the two
schedules in the same units as fps_executed: sequential should track
fps_eq5, pipelined should land nearer fps_eq6 (the ISSUE 2 acceptance).

Every search + lowering below goes through the one compile façade
(``repro.api``): ``CompileSpec(strategy="dse"|"autotune"|"manual-plan",
mode="reference"|"staged"|"pipelined")`` -> ``Compiled`` — the benchmark
measures exactly what ``repro.compile`` hands users.

``--autotune`` runs the closed loop instead (``repro.optim.autotune``): the
default DSE plan seeds an SA search whose every candidate is *executed*
through the pipelined streamer, and the candidate trajectory lands as
``autotune/...`` CSV rows (schema ``AUTOTUNE_SCHEMA``) plus a JSON artifact
(``--autotune-json``) with per-candidate predicted-vs-measured fps and the
latency-model calibration report.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import CompileSpec, build_plan, compile as smof_compile
from repro.compile_cache import enable_compile_cache
from repro.core import DSEConfig, EXEC_MODELS
from repro.core.resources import Device
from repro.memory import POLICIES, ChannelConfig
from repro.optim.autotune import AutotuneConfig
from repro.runtime.streamer import (eq5_sequential_time, eq6_pipeline_time,
                                    measured_stage_latencies)

from .common import emit, timeit

# A deliberately memory-starved streaming-device view: small enough that
# the exec graphs cannot hold their skip buffers + weights on-chip, so
# Algorithm 1 is forced into eviction and fragmentation.
TINY_STREAM = Device("tiny_stream", compute_units=4096,
                     onchip_bits=300_000, offchip_gbps=64.0,
                     freq_mhz=500.0, reconfig_s=0.0)

# All three paper topologies in executable form, via the one registry
# (core.builders.EXEC_MODELS); input shapes come from the graphs' own
# exec specs, not a parallel table.
MODEL_NAMES = tuple(EXEC_MODELS)

# Two plan flavours per (model, codecs):
#   ("output",)       one stage -> the DSE is forced into eviction and
#                     fragmentation (the paper's spill story; pipelined
#                     execution degenerates to a batched scan);
#   ("pool", "conv")  multi-stage -> stage-boundary spills and something
#                     for the pipeline to actually overlap (the Eq. 6 story).
CUT_VARIANTS = (("output",), ("pool", "conv"))

ROW_SCHEMA = ("executor", "model", "codecs", "kernel_mode", "n_stages",
              "microbatches", "fps_executed", "fps_eq5", "fps_eq6", "rel_err",
              "offchip_kbits", "evicted", "fragged", "channel_policy",
              "fps_contended_eq6", "prefetch_deadline_misses")


def _row(executor: str, model: str, codecs: tuple, plan, report,
         fps_executed: float, fps_eq5: float, fps_eq6: float,
         rel_err: float, microbatches: int, mem=None,
         kernel_mode: str = "auto") -> dict:
    # contended-Eq.6 estimate: fps_eq6 (measured-latency units) scaled by
    # the memory model's analytic contention slowdown; a starved stream
    # (infinite contended cycles) predicts zero throughput
    fps_cont = fps_eq6
    misses = 0
    policy = "none"
    if mem is not None:
        policy = mem.config.policy
        cont = mem.eq6_contended_cycles
        fps_cont = (fps_eq6 * mem.eq6_cycles / cont
                    if (cont > 0 and cont != float("inf")) else 0.0)
        misses = mem.prefetch.deadline_misses
    return {
        "executor": executor,
        "model": model,
        "codecs": "+".join(codecs),
        "kernel_mode": kernel_mode,
        "n_stages": plan.n_stages,
        "microbatches": microbatches,
        "fps_executed": fps_executed,
        "fps_eq5": fps_eq5,
        "fps_eq6": fps_eq6,
        "rel_err": rel_err,
        "offchip_kbits": report.total_offchip_bits / 1e3,
        "evicted": sum(1 for s in plan.streams if s.evicted),
        "fragged": sum(1 for lp in plan.layers.values()
                       if lp.weight_static_fraction < 1.0),
        "channel_policy": policy,
        "fps_contended_eq6": fps_cont,
        "prefetch_deadline_misses": misses,
    }


def _derived(r: dict, schema: tuple, exclude: tuple) -> str:
    """key=value derived-metrics string shared by every CSV row family."""
    return " ".join(
        f"{k}={r[k]:.4g}" if isinstance(r[k], float) else f"{k}={r[k]}"
        for k in schema if k not in exclude)


def _emit_row(r: dict, us_per_call: float) -> None:
    emit(f"e2e/{r['model']}_{r['codecs']}_s{r['n_stages']}_{r['executor']}"
         f"_{r['kernel_mode']}",
         us_per_call, _derived(r, ROW_SCHEMA, ("model", "codecs")))


SEED = 0  # all bench inputs derive from PRNGKey(SEED); stamped in the JSON


def run(smoke: bool = False, pipelined: bool = False,
        microbatches: int = 8, json_path: str | None = None,
        trace_path: str | None = None,
        channel: str | None = "weighted-fair",
        kernel_modes: tuple[str, ...] = ("auto",)) -> list[dict]:
    rows: list[dict] = []
    model_check = None
    np.random.seed(SEED)  # nothing below should draw host randomness, but
    #                       pin it anyway so rows are bit-reproducible
    names = MODEL_NAMES[:1] if smoke else MODEL_NAMES
    repeats = 3 if smoke else 5
    for name in names:
        # everything below goes through the one compile façade: the dense
        # reference is codec/kernel-mode independent, so it is compiled
        # once per model (reference dispatch is the numerical target)
        ref = smof_compile(CompileSpec(model=name, device=TINY_STREAM,
                                       mode="reference"))
        in_shape = ref.input_shape()
        x = jax.random.normal(jax.random.PRNGKey(SEED), in_shape,
                              jnp.float32)
        yr = ref.run(x).block_until_ready()
        for codecs, cut_kinds, km in (
                (c, k, km) for c in (("none",), ("none", "bfp8"))
                for k in CUT_VARIANTS for km in kernel_modes):
            staged = smof_compile(CompileSpec(
                model=name, device=TINY_STREAM, strategy="dse", mode="staged",
                kernel_mode=km,
                dse=DSEConfig(batch=1, codecs=codecs, word_bits=16,
                              cut_kinds=cut_kinds)))
            plan, low = staged.plan, staged.executor
            yl = staged.run(x).block_until_ready()
            rel = float(jnp.abs(yl - yr).max() / jnp.abs(yr).max())

            B = microbatches
            # same plan, pipelined — no re-search, just a re-lowering;
            # the channel model arbitrates the plan's off-chip traffic
            piped = smof_compile(dataclasses.replace(
                staged.spec, mode="pipelined", strategy="manual-plan",
                plan=plan, microbatches=B,
                channel=(ChannelConfig(policy=channel) if channel else None)))
            sx = piped.executor
            mem = sx.report.memory
            lat = measured_stage_latencies(sx, x)  # compiles stage fns only
            fps_eq5 = 1.0 / eq5_sequential_time(lat)
            fps_eq6 = 1.0 / eq6_pipeline_time(lat)

            us_seq = timeit(lambda: low(x).block_until_ready(),
                            repeats=repeats, warmup=1)
            rows.append(_row("sequential", name, codecs, plan, low.report,
                             1e6 / us_seq, fps_eq5, fps_eq6, rel, 1,
                             kernel_mode=km))
            _emit_row(rows[-1], us_seq)

            if pipelined:
                xs = jnp.broadcast_to(x, (B,) + in_shape)
                us_stream = timeit(lambda: sx(xs).block_until_ready(),
                                   repeats=repeats, warmup=1)
                us_frame = us_stream / B
                ys = np.asarray(sx(xs))
                rel_p = float(np.abs(ys[0] - np.asarray(yr)).max()
                              / np.abs(np.asarray(yr)).max())
                rows.append(_row("pipelined", name, codecs, plan, sx.report,
                                 1e6 / us_frame, fps_eq5, fps_eq6, rel_p, B,
                                 mem=mem, kernel_mode=km))
                _emit_row(rows[-1], us_frame)

                # --trace: narrate the first multi-stage pipelined config
                # (per-tick spans + ModelCheck) into a Chrome trace file
                if (trace_path and model_check is None
                        and plan.n_stages > 1):
                    _, mc = piped.trace(x, path=trace_path)
                    model_check = mc.summary()
                    emit(f"e2e/{name}_{'+'.join(codecs)}"
                         f"_s{plan.n_stages}_trace",
                         us_frame,
                         f"ok={mc.ok} ticks={mc.ticks_measured} "
                         f"steady={mc.steady_measured} "
                         f"max_rel_err={mc.max_stage_rel_err:.4g} "
                         f"bottleneck={mc.bottleneck_predicted}")

    if json_path:
        from .baseline import git_sha
        with open(json_path, "w") as f:
            json.dump({"schema": list(ROW_SCHEMA), "rows": rows,
                       "model_check": model_check,
                       "generated_unix": time.time(),
                       "git_sha": git_sha(), "seed": SEED,
                       "backend": jax.default_backend()}, f, indent=1)
    return rows


# =============================================================================
# Closed-loop autotune mode (--autotune)
# =============================================================================

# the per-candidate trajectory row schema ("model" + AutotuneResult
# .trajectory_rows()); one CSV line per candidate under autotune/<model>/
AUTOTUNE_SCHEMA = ("model", "candidate", "move", "accepted", "best_so_far",
                   "n_stages", "evicted", "fragged", "fps_measured",
                   "fps_eq6_pre", "fps_eq6_cal", "bottleneck_stage")

# smoke = the ISSUE 3 acceptance pair: UNet + the hardest memory-wall case
AUTOTUNE_SMOKE_MODELS = ("unet_exec", "x3d_exec")


def run_autotune(smoke: bool = False, microbatches: int = 8,
                 candidates: int | None = None,
                 json_path: str | None = None) -> dict:
    """Run the measured-in-the-loop autotuner per model; emit the candidate
    trajectory as CSV rows and (optionally) one JSON artifact."""
    names = AUTOTUNE_SMOKE_MODELS if smoke else MODEL_NAMES
    cfg = AutotuneConfig(
        n_candidates=candidates or (8 if smoke else 16),
        microbatches=microbatches,
        repeats=2 if smoke else 3,
        kernel_mode="auto")
    out = {"schema": list(AUTOTUNE_SCHEMA), "rows": [], "summaries": {}}
    for name in names:
        # the search half of the façade only: the autotuner already lowered
        # and measured every candidate, so compiling (= re-lowering) the
        # winner here would be pure wasted jit time
        _, res = build_plan(CompileSpec(
            model=name, device=TINY_STREAM, strategy="autotune",
            mode="pipelined", autotune_cfg=cfg, microbatches=microbatches))
        for r in res.trajectory_rows():
            row = {"model": name, **r}
            out["rows"].append(row)
            emit(f"autotune/{name}/cand{row['candidate']}",
                 1e6 / max(row["fps_measured"], 1e-30),
                 _derived(row, AUTOTUNE_SCHEMA, ("model", "candidate")))
        s = res.summary()
        out["summaries"][name] = s
        emit(f"autotune/{name}/best", 1e6 / max(res.best_fps, 1e-30),
             f"baseline_fps={res.baseline_fps:.4g} "
             f"best_fps={res.best_fps:.4g} speedup={s['speedup']:.4g} "
             f"pre_err={res.calibration.pre_err:.4g} "
             f"post_err={res.calibration.post_err:.4g} "
             f"calibrated={res.calibration.improved}")
    if json_path:
        out["generated_unix"] = time.time()
        out["backend"] = jax.default_backend()
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1)
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.e2e_executor")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--pipelined", action="store_true",
                    help="also run the pipelined streaming executor")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows as a JSON artifact")
    ap.add_argument("--autotune", action="store_true",
                    help="run the closed-loop autotuner instead of the "
                         "fixed DSE-plan sweep")
    ap.add_argument("--candidates", type=int, default=None,
                    help="autotune candidate budget (default 8 smoke / 16)")
    ap.add_argument("--autotune-json", default=None, metavar="PATH",
                    help="write the autotune trajectory as a JSON artifact")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="with --pipelined: write a Chrome trace (per-tick "
                         "spans + ModelCheck) of the first multi-stage "
                         "config; open in Perfetto / chrome://tracing")
    ap.add_argument("--channel", default="weighted-fair",
                    choices=list(POLICIES) + ["none"],
                    help="off-chip channel arbitration policy for the "
                         "pipelined compile ('none' disables the model)")
    ap.add_argument("--kernel-mode", default="auto",
                    choices=("auto", "pallas", "reference", "both"),
                    help="kernel dispatch for the measured compiles; "
                         "'both' emits comparable reference and pallas "
                         "rows per bench point (default auto)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.autotune:
        run_autotune(smoke=args.smoke, microbatches=args.microbatches,
                     candidates=args.candidates,
                     json_path=args.autotune_json)
        return
    run(smoke=args.smoke, pipelined=args.pipelined,
        microbatches=args.microbatches, json_path=args.json,
        trace_path=args.trace if args.pipelined else None,
        channel=None if args.channel == "none" else args.channel,
        kernel_modes=(("reference", "pallas") if args.kernel_mode == "both"
                      else (args.kernel_mode,)))


if __name__ == "__main__":
    main()
