"""The harness's own spans, compile counting, and the profiler window.

Spans time what the harness does around each call into the system (the
driver names them, as ``dispatch``, ``wait`` and ``gen``) on the host
clock.  While the profiler is on, each span is also a ``TraceAnnotation``,
so the trace holds it on the device trace's clock and the reduction can
say what the host was doing in each idle gap of the device.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import shutil
import tempfile
import time

import jax

# The span that brackets the traced part of the window.
WINDOW_SPAN = "traced"


class Spans:
    def __init__(self) -> None:
        self.seconds: collections.Counter = collections.Counter()
        self.count: collections.Counter = collections.Counter()
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            if self.tracing:
                with jax.profiler.TraceAnnotation(name):
                    yield
            else:
                yield
        finally:
            self.seconds[name] += time.perf_counter() - t0
            self.count[name] += 1


class CompileCounter:
    """Persistent-cache hits and misses and backend compiles, from JAX's
    monitoring events, so a run can say how many compiles fell in its
    window (there should be none)."""

    def __init__(self) -> None:
        self.counts: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


class Profiler:
    """One traced part of the window: ``start`` turns the profiler and the
    span annotations on, ``stop`` turns them off; ``xplane`` is the trace
    file, under a temporary directory that ``close`` removes."""

    def __init__(self, spans: Spans) -> None:
        self.spans = spans
        self.dir = None
        self.xplane = None
        self._window = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.tracing = True
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self.t0 = time.perf_counter()

    @property
    def on(self) -> bool:
        return self._window is not None

    def stop(self) -> None:
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        self._window = None
        self.spans.tracing = False
        jax.profiler.stop_trace()
        found = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one trace file, found {found}")
        self.xplane = found[0]

    def close(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


def delta(before: dict, after: dict) -> dict:
    """``after - before`` per key, for counter snapshots."""
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in sorted(set(before) | set(after))}


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn with the run's seeded generator (Algorithm R)."""

    def __init__(self, k: int, rng) -> None:
        self.k = k
        self.rng = rng
        self.seen = 0
        self.items: list = []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = item
