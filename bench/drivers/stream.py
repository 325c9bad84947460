"""Closed loop: back-to-back pipelined calls over a pool of distinct frames.

Each call is ``Compiled.run`` of one batch of B frames, ``(B,) +
reference.input_shape(net)``, cycling through a pool of ``pool_batches``
batches made on the device from the seed.  The loop keeps ``in_flight``
calls dispatched: once that many are out, it waits for the oldest before
it sends the next, so the device never waits for the host's round trip.
When the window's time is up nothing more is sent; the loop waits for
every call it sent and reads the clock after that wait.  ``fps`` is every
frame of those calls over that whole time.  A seeded sample of the
window's calls is kept, output and input, for the comparison with the
reference.
"""
from __future__ import annotations

import collections
import time

import jax

from bench import reference
from bench.observe import Profiler, Reservoir, delta


def run(ctx) -> dict:
    c, tr = ctx.system, ctx.traffic
    B = c.executor.microbatches
    pool = reference.make_frames(ctx.net, ctx.frame_key,
                                 (tr["pool_batches"], B),
                                 ctx.cfg["frame_channels"])
    batches = [pool[i] for i in range(tr["pool_batches"])]
    del pool
    jax.block_until_ready(batches)
    for i in range(tr["warm_calls"]):
        with ctx.spans("warm"):
            jax.block_until_ready(c.run(batches[i % len(batches)]))
    setup_s = time.perf_counter() - ctx.t_start
    compiles0 = ctx.compiles.snapshot()

    sample = Reservoir(tr["sample_calls"], ctx.rng)
    prof = Profiler(ctx.spans) if ctx.trace else None
    pending: collections.deque = collections.deque()
    calls = 0

    def complete() -> None:
        k, y = pending.popleft()
        with ctx.spans("wait"):
            y.block_until_ready()
        with ctx.spans("gen"):
            sample.offer((k, y))

    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        now = time.perf_counter() - t0
        if prof is not None and prof.xplane is None and not prof.on \
                and now >= tr["trace_after"] * ctx.seconds:
            prof.start()
        if prof is not None and prof.on \
                and time.perf_counter() - prof.t0 >= tr["trace_seconds"]:
            prof.stop()
        if len(pending) >= tr["in_flight"]:
            complete()
        k = calls % len(batches)
        with ctx.spans("dispatch"):
            pending.append((k, c.run(batches[k])))
        calls += 1
    while pending:
        complete()
    t1 = time.perf_counter()
    if prof is not None and prof.on:
        prof.stop()
    frames = calls * B
    ctx.log(f"window: {calls} calls of B={B}, {tr['in_flight']} in flight, "
            f"{frames} frames in {t1 - t0:.3f} s")
    return {
        "setup_s": setup_s,
        "end_to_end": {"fps": frames / (t1 - t0)},
        "attempted": frames, "failed": 0,
        "compiles_in_window": delta(compiles0, ctx.compiles.snapshot()),
        "samples": [(batches[k][b], y[b]) for k, y in sample.items
                    for b in range(B)],
        "profiler": prof,
        "batch": B,
    }
