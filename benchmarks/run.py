"""Benchmark driver — one function per paper table/figure, plus the
end-to-end executor benchmark.

CSV output schema (one line per benchmark point, written to stdout):

    name,us_per_call,derived

  name          ``<section>/<point>`` — section matches the paper artefact
                (``table3``, ``table4``, ``table5``, ``fig6``, ``fig7``,
                ``fig8``, ``kernels``), ``e2e`` for the
                executed-pipeline benchmark, or ``autotune`` for the
                closed-loop candidate trajectory (``--autotune``).
  us_per_call   median wall-clock microseconds of the timed callable
                (DSE solve, kernel invocation, or jitted pipeline step;
                0 where the point is analytic only).
  derived       space-separated ``key=value`` metrics specific to the
                point (fps, GMACs/s, compression ratios, rel_err, ...).

The first line is the literal header ``name,us_per_call,derived``; all
diagnostics go to stderr, so stdout is directly machine-readable.

Modes:
    python -m benchmarks.run            # full sweep
    python -m benchmarks.run --smoke    # CI-sized subset (CPU-friendly)
    python -m benchmarks.run --smoke --pipelined --e2e-json out.json
                                        # sequential vs pipelined executor
                                        # rows in one JSON artifact (CI)
    python -m benchmarks.run --smoke --autotune --autotune-json tune.json
                                        # + the closed-loop autotuner's
                                        # candidate trajectory (autotune/...
                                        # rows, schema in e2e_executor.py)
    python -m benchmarks.run --smoke --pipelined --baseline BENCH_smoke.json
                                        # snapshot e2e rows as a committed
                                        # baseline (git SHA + timestamp)
    python -m benchmarks.run --smoke --pipelined \
                             --check-baseline BENCH_smoke.json
                                        # regression gate: exits 1 if any
                                        # row breaks the per-metric
                                        # tolerances (benchmarks/baseline.py)
"""
from __future__ import annotations

import argparse
import sys


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.run",
                                 description="SMOF benchmark driver")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized subset (table3 + e2e) instead of the "
                         "full sweep")
    ap.add_argument("--pipelined", action="store_true",
                    help="also run the pipelined streaming executor in the "
                         "e2e section")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="stream length B for the pipelined executor")
    ap.add_argument("--e2e-json", default=None, metavar="PATH",
                    help="write the e2e rows as a JSON artifact")
    ap.add_argument("--autotune", action="store_true",
                    help="also run the closed-loop autotuner in the e2e "
                         "section (candidate-trajectory rows)")
    ap.add_argument("--autotune-json", default=None, metavar="PATH",
                    help="write the autotune trajectory as a JSON artifact")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="write the e2e rows as a baseline artifact "
                         "(BENCH_*.json, stamped with git SHA + timestamp)")
    ap.add_argument("--check-baseline", default=None, metavar="PATH",
                    help="compare the e2e rows against a committed baseline "
                         "under per-metric tolerances; exit 1 on regression")
    ap.add_argument("--kernel-mode", default="auto",
                    choices=("auto", "pallas", "reference", "both"),
                    help="kernel dispatch for the e2e compiles; 'both' "
                         "emits comparable reference and pallas rows per "
                         "bench point (default auto)")
    args = ap.parse_args(argv)
    smoke = args.smoke
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (baseline, e2e_executor, fig6_ablation, fig7_compression,
                   fig8_variability, kernels_bench, table3_models,
                   table4_partitioning, table5_throughput)
    print("name,us_per_call,derived")
    table3_models.run()
    e2e_rows = e2e_executor.run(
        smoke=smoke, pipelined=args.pipelined,
        microbatches=args.microbatches, json_path=args.e2e_json,
        kernel_modes=(("reference", "pallas") if args.kernel_mode == "both"
                      else (args.kernel_mode,)))
    if args.baseline:
        p = baseline.write_baseline(e2e_rows, args.baseline,
                                    note="smoke" if smoke else "full")
        print(f"baseline: wrote {len(e2e_rows)} rows -> {p}", file=sys.stderr)
    if args.check_baseline:
        failures, notes = baseline.check_baseline(e2e_rows,
                                                  args.check_baseline)
        for line in notes:
            print(f"baseline: {line}", file=sys.stderr)
        if failures:
            for line in failures:
                print(f"baseline REGRESSION: {line}", file=sys.stderr)
            raise SystemExit(1)
        print("baseline: all rows within tolerance", file=sys.stderr)
    if args.autotune:
        e2e_executor.run_autotune(smoke=smoke,
                                  microbatches=args.microbatches,
                                  json_path=args.autotune_json)
    if smoke:
        return
    table4_partitioning.run()
    fig6_ablation.run()
    fig7_compression.run()
    fig8_variability.run()
    table5_throughput.run()
    kernels_bench.run()


if __name__ == "__main__":
    main()
