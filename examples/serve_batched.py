"""End-to-end serving driver (SMOF is an inference toolflow).

Compiles an ``EXEC_MODELS`` streaming graph through ``repro.compile`` (the
same ``--channel``/``--channel-gbps`` knobs as quickstart, docs/MEMORY.md)
and serves frames through ``Compiled.serve``; the summary prints the
off-chip per-stream bandwidth table and prefetch deadline misses:

    PYTHONPATH=src python examples/serve_batched.py --model unet_exec \
        --channel weighted-fair --channel-gbps 0.5 --onchip-kbits 300
"""
import argparse
import dataclasses
import time

import numpy as np

from repro.api import add_compile_args, spec_from_args
from repro.core import EXEC_MODELS


def serve_graph(args) -> None:
    """Serve a compiled streaming graph and report the channel split."""
    import repro
    from repro.core.resources import get_device

    spec = spec_from_args(args, microbatches=4)
    if args.onchip_kbits is not None:
        spec = dataclasses.replace(spec, device=dataclasses.replace(
            get_device(args.device), onchip_bits=args.onchip_kbits * 1e3))
    c = repro.compile(spec)
    srv = c.serve()
    x = np.zeros(c.input_shape(), np.float32)
    t0 = time.time()
    for _ in range(args.requests):
        srv.submit(x)
    srv.flush()
    dt = time.time() - t0
    st = srv.stats
    print(f"served {st.frames_out}/{st.frames_in} frames of {args.model} "
          f"({c.mode}, {args.device}) in {dt:.2f}s "
          f"({st.frames_out / dt:.1f} fps)")

    mem = getattr(getattr(c.executor, "report", None), "memory", None)
    if mem is None:
        print("\nno off-chip channel model attached "
              "(pass --channel / --channel-gbps, see docs/MEMORY.md)")
        return
    arb = mem.arbitration
    print(f"\noff-chip channel ({mem.config.policy}, "
          f"{mem.channel.gbps:g} Gbps, utilization {arb.utilization:.0%}):")
    print(f"  {'stream':<28} {'kind':<20} {'demand':>9} {'granted':>9}  ok")
    for r in mem.stream_table():
        print(f"  {r['name']:<28} {r['kind']:<20} "
              f"{r['demand_gbps']:>7.2f}G {r['granted_gbps']:>7.2f}G"
              f"  {'yes' if r['satisfied'] else 'NO'}")
    misses = mem.prefetch.deadline_misses
    print(f"  prefetch deadline misses: {misses}"
          + (f" {mem.prefetch.misses_by_stage()}" if misses else ""))
    print(f"  contended Eq.6: {mem.eq6_contended_cycles:g} cycles "
          f"(uncontended {mem.eq6_cycles:g})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    # brings --model/--device/--mode/--channel/--channel-gbps along
    # (docs/MEMORY.md)
    add_compile_args(ap, default_model="unet_exec", default_mode="pipelined",
                     models=EXEC_MODELS, modes=("staged", "pipelined"))
    ap.add_argument("--onchip-kbits", type=float, default=None,
                    help="shrink the on-chip view so the DSE evicts/streams "
                         "(as in quickstart)")
    serve_graph(ap.parse_args())


if __name__ == "__main__":
    main()
