#!/usr/bin/env python3
"""Smoke run of the SMOF compile -> execute -> serve path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the shard_map ring on four chips

One chip: the executable UNet at the paper's 368x480 frame (base width 64,
five levels, channels 64..1024) goes through ``repro.compile`` four times,
in one process:

1. the dense f32 reference at highest matmul precision;
2. the DSE plan for the TPU v5e kernel view (on-chip = VMEM), staged, with
   the Pallas kernels — its long skips are evicted BFP8-compressed — and
   an all-lossless twin of the same plan;
3. the same plan pipelined over 8 microbatches;
4. the server in front of the pipelined compile, answering 12 frames.

Every output is checked against the reference.  ``--chips 4`` runs only a
4-stage cut of the same plan on the ``shard_map`` ring, frame by frame
against the interleaved executor on device 0.

Earlier lines report compile and warm times, the plan's evicted and
fragmented edges, which vertices ran a Pallas body, and whether the staged
step's HLO puts the evicted payloads in host memory.  The last line is one
JSON object naming the device.  Any failure exits non-zero, and so does a
run on a machine where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.builders import build_unet_exec, exec_input_shape  # noqa: E402
from repro.core.dse import DSEConfig  # noqa: E402
from repro.core.plan import ExecutionPlan  # noqa: E402
from repro.core.resources import TPU_V5E_KERNEL  # noqa: E402
from repro.runtime.executor import (analyze_plan, resolve_kernel_mode,  # noqa: E402
                                    vertex_body)
from repro.testing.oracle import bfp8_l2_bound, lossless_twin  # noqa: E402

SEED = 0
UNET = dict(positions=368 * 480, cin=32, base=64, levels=5, n_classes=32)
DSE = DSEConfig(batch=1, codecs=("none", "bfp8"), word_bits=16,
                cut_kinds=("pool", "conv"))
MICROBATCHES = 8
SERVED_FRAMES = 12
RING_STAGES = 4
# The all-lossless twin computes the reference's function with the
# executed plan's matmuls: Pallas and XLA dots at the TPU's default
# precision, one bf16 pass per product (relative rounding 2^-9 per
# operand) compounding over fourteen conv layers, against the reference's
# highest-precision f32 dots.  On a v5e that puts the twin 4.0e-3 from the
# reference (relative L2); 1e-2 leaves room for that and still catches a
# wrong kernel, which lands at order 1.
LOSSLESS_REL_L2 = 1e-2
# The ring and the interleaved scan run the same stage functions with the
# same kernels; only XLA's fusion of the code around them differs.  A
# last-bit difference that crosses a BFP8 rounding boundary flips one
# mantissa step of an evicted skip, about 5e-5 of the output norm; 1e-3
# allows a few such flips and is still far below the plan's own distance
# from the reference.
RING_REL_L2 = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu() -> dict:
    """The device as JAX reports it; exits unless it is a TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def count_cache_events() -> collections.Counter:
    """Persistent compile-cache ``hits`` and ``misses`` from JAX's
    monitoring events, so the run says whether its executables came from
    disk."""
    counts = collections.Counter()

    def on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1
    jax.monitoring.register_event_listener(on_event)
    return counts


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def first_and_warm(phase: str, fn, *args):
    """Call twice: the first call traces and compiles, the second is the
    warm wall-clock time of the phase."""
    _, first = timed(fn, *args)
    out, warm = timed(fn, *args)
    log(f"[{phase}] first call {first:.3f} s (trace + compile + run), "
        f"warm {warm:.4f} s, compile ~{first - warm:.3f} s")
    return out


def rel_l2(y: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(y - ref) / np.linalg.norm(ref))


def check_bfp8(what: str, y, ref: np.ndarray, n_lossy: int) -> None:
    """The ``bfp8_bounded`` oracle's L2 limit against the f32 reference."""
    y = np.asarray(y)
    if y.shape != ref.shape or not np.all(np.isfinite(y)):
        raise AssertionError(f"{what}: shape {y.shape} (want {ref.shape}) "
                             f"or non-finite values")
    err = float(np.linalg.norm(y - ref))
    bound = bfp8_l2_bound(float(np.linalg.norm(ref)), n_lossy)
    log(f"  {what}: L2 err {err:.6g} (rel {rel_l2(y, ref):.3e}), "
        f"bfp8 bound {bound:.6g}")
    if err > bound:
        raise AssertionError(f"{what}: L2 error {err} over bound {bound}")


def make_frames(g, n: int) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(SEED),
                             (n,) + exec_input_shape(g), jnp.float32)


def dse_spec(g) -> repro.CompileSpec:
    return repro.CompileSpec(model=g, device=TPU_V5E_KERNEL, strategy="dse",
                             mode="staged", kernel_mode="pallas", dse=DSE,
                             microbatches=MICROBATCHES, seed=SEED)


def report_plan(g, plan: ExecutionPlan) -> list:
    """Log the plan's eviction/fragmentation and each vertex's body;
    returns the BFP8-evicted streams after asserting a long skip is one."""
    use_pallas, interpret = resolve_kernel_mode("pallas", None)
    assert use_pallas is True and interpret is False, (use_pallas, interpret)
    evicted = [s for s in plan.streams if s.evicted]
    lossy = [s for s in evicted if s.codec == "bfp8"]
    frag = [n for n, lp in plan.layers.items()
            if lp.weight_static_fraction < 1.0]
    log(f"plan: {plan.n_stages} stage(s), {len(evicted)} evicted edges "
        f"({len(lossy)} bfp8): "
        + ", ".join(f"{s.src}->{s.dst}[{s.codec}]" for s in evicted))
    log(f"plan: {len(frag)} fragmented weights: {', '.join(frag)}")
    long_skips = [s for s in lossy if g.vertex(s.dst).kind == "concat"]
    assert long_skips, "the plan evicts no long skip with the bfp8 codec"
    an = analyze_plan(g, plan, use_pallas=use_pallas, interpret=interpret)
    bodies = {n: vertex_body(g, n, an) for n in an.topo}
    pallas = [n for n, b in bodies.items() if b == "pallas"]
    ref = [f"{n}({g.vertex(n).kind})" for n, b in bodies.items()
           if b == "reference"]
    log(f"bodies: {len(pallas)} pallas: {', '.join(pallas)}")
    log(f"bodies: {len(ref)} reference: {', '.join(ref)}")
    return lossy


def host_placements(lowered_text: str) -> int:
    return lowered_text.count('_xla_buffer_placement = "pinned_host"')


def one_chip() -> None:
    g = build_unet_exec(**UNET)
    frames = make_frames(g, SERVED_FRAMES)
    log(f"model: unet_exec {UNET}, input {exec_input_shape(g)}, "
        f"{len(g.topo())} vertices")

    # -- 1. reference ---------------------------------------------------------
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        ref_c = repro.compile(repro.CompileSpec(model=g, mode="reference",
                                                seed=SEED))
        log(f"[reference] repro.compile {time.perf_counter() - t0:.3f} s")
        first_and_warm("reference", ref_c.run, frames[0])
        refs = [np.asarray(ref_c.run(frames[i]))
                for i in range(SERVED_FRAMES)]

    # -- 2. DSE plan, staged ---------------------------------------------------
    spec = dse_spec(g)
    t0 = time.perf_counter()
    staged = repro.compile(spec)
    log(f"[staged] repro.compile (DSE + lowering) "
        f"{time.perf_counter() - t0:.3f} s")
    plan = staged.plan
    lossy = report_plan(g, plan)
    y = first_and_warm("staged", staged.run, frames[0])
    check_bfp8("staged frame 0", y, refs[0], len(lossy))

    ex = staged.executor
    n_host = host_placements(ex.fn.lower(ex.params, frames[0]).as_text())
    log(f"[staged] HLO: {n_host} pinned_host placements for "
        f"{len(lossy)} bfp8-evicted edges "
        f"(payloads in host memory: {n_host >= 2 * len(lossy)})")
    assert n_host >= 2 * len(lossy), n_host

    twin = repro.compile(dataclasses.replace(
        spec, strategy="manual-plan", plan=lossless_twin(plan)))
    y = np.asarray(first_and_warm("staged lossless twin", twin.run,
                                  frames[0]))
    err = rel_l2(y, refs[0])
    log(f"  lossless twin frame 0: rel L2 {err:.3e} "
        f"(limit {LOSSLESS_REL_L2:g})")
    assert np.all(np.isfinite(y)) and err <= LOSSLESS_REL_L2, err

    # -- 3. pipelined -----------------------------------------------------------
    pipe = repro.compile(dataclasses.replace(
        spec, strategy="manual-plan", plan=plan, mode="pipelined",
        placement="interleave"))
    xs = frames[:MICROBATCHES]
    ys = np.asarray(first_and_warm(f"pipelined B={MICROBATCHES}", pipe.run,
                                   xs))
    for b in range(MICROBATCHES):
        check_bfp8(f"pipelined frame {b}", ys[b], refs[b], len(lossy))

    # -- 4. server ---------------------------------------------------------------
    srv = pipe.serve()
    tickets = [srv.submit(np.asarray(frames[i]))
               for i in range(SERVED_FRAMES)]
    t0 = time.perf_counter()
    out = srv.flush()
    log(f"[served] flush of {SERVED_FRAMES} frames "
        f"{time.perf_counter() - t0:.4f} s (warm: same executable)")
    assert sorted(out) == sorted(tickets), (sorted(out), tickets)
    for i, t in enumerate(tickets):
        y = srv.result(t)
        if i < MICROBATCHES:      # same executable, same stream position
            assert np.array_equal(y, ys[i]), f"served frame {i} != pipelined"
        check_bfp8(f"served frame {i}", y, refs[i], len(lossy))


def ring_plan(g, plan: ExecutionPlan) -> ExecutionPlan:
    """The DSE plan's decisions on a 4-stage cut: contiguous runs of the
    topological order, so every edge goes forward across stages."""
    ring = ExecutionPlan.from_json(plan.to_json())
    topo = g.topo()
    for i, n in enumerate(topo):
        ring.layers[n].stage = i * RING_STAGES // len(topo)
    ring.n_stages = RING_STAGES
    ring.validate()
    return ring


def four_chips() -> None:
    g = build_unet_exec(**UNET)
    xs = make_frames(g, MICROBATCHES)
    spec = dse_spec(g)
    plan, _ = repro.build_plan(spec, g)
    report_plan(g, plan)
    ring = ring_plan(g, plan)
    runs = {}
    for placement in ("interleave", "shard_map"):
        c = repro.compile(dataclasses.replace(
            spec, strategy="manual-plan", plan=ring, mode="pipelined",
            placement=placement))
        assert c.executor.placement == placement
        runs[placement] = np.asarray(first_and_warm(
            f"{placement} {RING_STAGES} stages B={MICROBATCHES}", c.run, xs))
    a, b = runs["interleave"], runs["shard_map"]
    for i in range(MICROBATCHES):
        err = rel_l2(b[i], a[i])
        log(f"  frame {i}: shard_map vs interleave rel L2 {err:.3e}, "
            f"bitwise equal {np.array_equal(a[i], b[i])}")
        assert np.all(np.isfinite(b[i])) and err <= RING_REL_L2, (i, err)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: reference/staged/pipelined/served phases; "
                         "4: only the shard_map ring against interleave")
    args = ap.parse_args(argv)
    device = require_tpu()
    if device["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {device['count']}")
    log(f"device: {device}, jax {jax.__version__}")
    log(f"compile cache: {enable_compile_cache()}")
    cache = count_cache_events()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"total {time.perf_counter() - t0:.1f} s; persistent compile cache "
        f"hits {cache['hits']}, misses {cache['misses']}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
