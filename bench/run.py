#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload unet368.stream --seed 7 --seconds 20 --trace 0

``BENCHMARK.json`` names the cell's configuration file and traffic mix.
The traffic's data file (``bench/traffic/<traffic>.json``) names the driver
under ``bench/drivers/`` that runs the window and holds its parameters.  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, each read by ``bench/metrics/<name>.py``
from a profiler trace of a steady part of the window and from the
harness's spans.

Every run compares a seeded sample of what the window produced with the
plain reference (``bench/reference.py``) once the window has closed.  The
last line of standard output is one JSON object; the numbers compared, each
beside its limit, close it and are the last lines on standard error.  A
machine where JAX finds no TPU, or fewer chips than the cell asks for,
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import reference  # noqa: E402
from bench.observe import CompileCounter, Spans  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) for a workload name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{', '.join(cells)}")
    cell = cells[workload]
    return (bench, cell, load_config(bench, cell["config"]),
            load_traffic(cell["traffic"]))


def load_config(bench: dict, config: str) -> dict:
    entry = {c["name"]: c for c in bench["configs"]}[config]
    return json.loads((ROOT / entry["file"]).read_text())


def load_traffic(traffic: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())


def require_device(chips: int) -> dict:
    """The device as JAX reports it; exits unless there are ``chips`` TPUs."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: JAX found no devices: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def check(cfg: dict, net: list, weights: dict, samples: list) -> dict:
    """The numbers compared, each with its limit: the largest relative L2
    distance of a sampled frame's output from the reference's."""
    rel = reference.rel_l2_fn(net, cfg["arithmetic"])
    errs = [float(rel(weights, jax.numpy.asarray(x), jax.numpy.asarray(y)))
            for x, y in samples]
    worst = max(errs) if errs else float("inf")
    if not math.isfinite(worst):         # no frame compared, or a non-finite
        worst = 1e30                     # output: JSON has no infinity
    return {"frame_rel_l2": {"value": worst,
                             "limit": cfg["limits"]["frame_rel_l2"],
                             "frames": len(errs)}}


def applies(metric: dict, cell: dict) -> bool:
    """Whether a metric of ``BENCHMARK.json`` is reported in a cell."""
    return cell["name"] in metric.get("workloads", [cell["name"]])


def per_layer(bench: dict, cell: dict, mctx) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    moves = {e["name"]: e for e in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if not (applies(m, cell) and applies(moves[m["moves"]], cell)):
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(mctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def setup(cfg: dict, seed: int) -> types.SimpleNamespace:
    """Everything a window needs before it opens: the reference's layers
    and seeded weights, the system under test serving them, the key for
    the frames and the seeded generator for the traffic."""
    from bench import system
    log(f"compile cache: {system.enable_compile_cache()}")
    # every program, the small ones that make weights and frames too, comes
    # from the cache after a cell's first run, so set-up is the same work
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    net = reference.model_layers(cfg)
    k_weights, k_frames = jax.random.split(reference.seed_key(seed))
    weights = reference.make_weights(net, k_weights)
    t = time.perf_counter()
    compiled = system.build(cfg, weights)
    log(f"repro.compile (search + lowering) {time.perf_counter() - t:.3f} s")
    for line in system.plan_lines(compiled):
        log(line)
    return types.SimpleNamespace(
        cfg=cfg, net=net, weights=weights, system=compiled,
        frame_key=k_frames, rng=np.random.default_rng(seed),
        t_start=T_START, spans=Spans(), compiles=compiles, log=log)


def run_cell(bench: dict, cell: dict, cfg: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool, device: dict,
             peaks: dict, keep_trace: pathlib.Path | None = None,
             wrap=None) -> dict:
    """One run of a cell on the devices JAX has; returns the result object.

    ``wrap(compiled)``, where given, is applied to the system under test
    before the window (the tests break the timed path with it)."""
    log(f"device: {device}, jax {jax.__version__}")
    ctx = setup(cfg, seed)
    if wrap is not None:
        wrap(ctx.system)
    ctx.__dict__.update(cell=cell, traffic=traffic, seconds=seconds,
                        trace=trace)
    compiles, net, weights = ctx.compiles, ctx.net, ctx.weights
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    out = driver.run(ctx)
    log(f"set-up {out['setup_s']:.3f} s; compile cache before the window: "
        f"{compiles.snapshot()}")
    log(f"compiles in the window: {out['compiles_in_window']}")
    device = dict(device, memory_peak_bytes=memory_peak_bytes(cell["chips"]))

    prof = out.pop("profiler")
    breakdown = None
    if trace:
        from bench import ops, system, trace_reduce
        hlo = system.step_hlo(ctx.system, net)
        instrs = ops.index(hlo) if hlo else None
        log(f"step HLO: {len(instrs or ())} instructions")
        try:
            red = trace_reduce.reduce_file(prof.xplane, instrs,
                                           spans=set(ctx.spans.count))
            if keep_trace is not None:
                keep_trace.mkdir(parents=True, exist_ok=True)
                stem = keep_trace / f"{cell['name']}.{seed}"
                shutil.copy(prof.xplane, f"{stem}.xplane.pb")
                if hlo:
                    pathlib.Path(f"{stem}.hlo.txt").write_text(hlo)
        finally:
            prof.close()
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        log(f"trace ops: {red.op_count(lambda o: o.instr is not None):.0f} "
            f"of {red.n_ops} named in the step's HLO; "
            f"{red.op_count(ops.is_conv):.0f} conv, "
            f"{red.op_count(ops.is_bfp8):.0f} bfp8, "
            f"{red.op_count(ops.is_hop):.0f} hop")
        log(f"trace: window {red.window_s:.6f} s, busy {red.busy_s:.6f} s, "
            f"{red.n_ops} device ops; top ops {breakdown['device_ops'][:4]}; "
            f"idle by host span {breakdown['idle_gaps'][:4]}")
        # the frames of the window: the step's executions in it, times B
        frames = red.step_calls() * out["batch"]
        log(f"trace: {frames:.3f} frames in the window")
        mctx = types.SimpleNamespace(
            trace=red, out=out, frames=frames, net=net, peaks=peaks,
            spans=ctx.spans, cfg=cfg, cell=cell)
        metrics = per_layer(bench, cell, mctx)
    else:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"] if applies(m, cell)}

    # the reference runs once the program's state is freed
    samples = out.pop("samples")
    attempted, failed = out["attempted"], out["failed"]
    del ctx, out
    gc.collect()
    checks = check(cfg, net, weights, samples)
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=pathlib.Path, default=None,
                    help="copy the traced run's .xplane.pb into this "
                         "directory")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = load_cell(args.workload)
    device = require_device(cell["chips"])
    peaks = load_peaks(device["kind"])
    result = run_cell(bench, cell, cfg, traffic, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      device=device, peaks=peaks, keep_trace=args.keep_trace)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({c['frames']} frames)", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
