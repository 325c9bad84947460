#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, or the control in its place.

    python bench/control.py --workload unet368.stream --what program \\
        --seeds 101 102 103 --seconds 4
    python bench/control.py --workload unet368.stream --what control \\
        --seeds 201 202 203 --seconds 4

Runs the cell once per seed in one process, at the cell's own size and
load with a short window, and prints the numbers ``correct`` compares, one
line per seed.  ``--what control`` puts the reference, in the stated
arithmetic but with everything held in bfloat16, in the place of the
system's pipelined step.  ``--arithmetic key=value`` compares against the
reference in another arithmetic than the configuration states, to find out
which one the program computes.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, run  # noqa: E402


def control_wrap(net, arith):
    ctl = reference.control_fn(net, arith)

    def wrap(compiled):
        compiled.executor.fn = lambda params, xs: ctl(params, xs)
    return wrap


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", choices=("program", "control"), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--arithmetic", nargs="*", default=[],
                    metavar="KEY=VALUE",
                    help="compare against this arithmetic (a JSON value)")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = run.load_cell(args.workload)
    for kv in args.arithmetic:
        key, value = kv.split("=", 1)
        try:
            cfg["arithmetic"][key] = json.loads(value)
        except json.JSONDecodeError:
            cfg["arithmetic"][key] = value
    device = run.require_device(cell["chips"])
    peaks = run.load_peaks(device["kind"])
    wrap = (control_wrap(reference.model_layers(cfg), cfg["arithmetic"])
            if args.what == "control" else None)
    for seed in args.seeds:
        res = run.run_cell(bench, cell, cfg, traffic, seed=seed,
                           seconds=args.seconds, trace=False, device=device,
                           peaks=peaks, wrap=wrap)
        print(json.dumps({"reading": args.what, "workload": cell["name"],
                          "arithmetic": cfg["arithmetic"],
                          "seed": seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
