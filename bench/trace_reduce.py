"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

The traced window is the harness's ``traced`` span on the host plane.
Within it, the device's operations are the events of the ``XLA Ops`` line
of each TPU plane; busy time is the union of their intervals, averaged over
the chips traced, and the idle share is one minus busy over the window.
Every idle gap of the device is labelled by the harness span the host was
in for most of the gap (the driver's own names, such as ``dispatch`` and
``wait``), or ``other``.

That line nests: a ``while`` (or ``conditional``, ``call``) spans the ops
of its body.  Such a container counts towards busy time, and not as an op
of its own in counts, totals and the breakdown.  Times of ops are clipped
to the window, and ``step_calls`` counts the executions of the step (the
program of the ``XLA Modules`` line with the most time in the window) in
it, each in the share of it that lies in the window, so that a quantity
per call or per frame divides the same window's numbers.

A TPU trace names each op by its whole HLO line (``%closed_call.89 =
f32[...] custom-call(...), ...``).  Readers of per-layer metrics classify
operations (``bench/ops.py``) by the instruction of the compiled step that
the line names, where the harness hands the reduction that step's HLO
(``Op.instr``), else by the line itself and the text of the op's stats
(``Op.hlo``).  An op's ``name`` is the line shortened (``ops.label``).
"""
from __future__ import annotations

import collections
import dataclasses
import re
import statistics

from bench import ops as op_classes
from bench.observe import WINDOW_SPAN

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
HOST_PLANE = "/host:CPU"
TOP = 10
# the stats of an op event that carry its HLO text and names
TEXT_STATS = ("long_name", "hlo_op", "tf_op", "kernel_details")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    end_ns: float
    hlo: str            # the op's HLO text where the trace carries it
    instr: object = None  # the compiled step's instruction of that name
    container: bool = False   # a while, conditional or call: spans others


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, t0, t1):
    return [(max(a, t0), min(b, t1)) for a, b in iv if b > t0 and a < t1]


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


@dataclasses.dataclass
class Reduction:
    t0_ns: float
    t1_ns: float
    ops: dict[int, list[Op]]                     # chip -> ops in the window
    host: list[tuple[str, float, float]]         # harness spans in the window
    modules: dict[int, list[tuple[str, float, float]]] = \
        dataclasses.field(default_factory=dict)  # chip -> programs run

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    @property
    def n_ops(self) -> int:
        return sum(not o.container for v in self.ops.values() for o in v)

    def _in_window(self, a: float, b: float) -> float:
        return max(0.0, min(b, self.t1_ns) - max(a, self.t0_ns))

    @property
    def chips(self) -> int:
        return max(len(self.ops), 1)

    def busy_intervals(self, chip: int) -> list[tuple[float, float]]:
        iv = [(o.start_ns, o.end_ns) for o in self.ops.get(chip, [])]
        return _clip(union(iv), self.t0_ns, self.t1_ns)

    @property
    def busy_s(self) -> float:
        tot = sum(b - a for c in self.ops
                  for a, b in self.busy_intervals(c))
        return tot * 1e-9 / self.chips

    def op_seconds(self, pred) -> float:
        """Device seconds in the window of the ops ``pred(op)`` selects,
        per chip."""
        return sum(self._in_window(o.start_ns, o.end_ns)
                   for ops in self.ops.values() for o in ops
                   if not o.container and pred(o)) * 1e-9 / self.chips

    def op_total(self, pred, value) -> float | None:
        """The sum of ``value(op)`` over the ops ``pred(op)`` selects, each
        in the share of its time that lies in the window, per chip; None
        where ``value`` gives None for one of them."""
        total = 0.0
        for ops in self.ops.values():
            for o in ops:
                if o.container or not pred(o) or o.end_ns <= o.start_ns:
                    continue
                v = value(o)
                if v is None:
                    return None
                total += v * self._in_window(o.start_ns, o.end_ns) / (
                    o.end_ns - o.start_ns)
        return total / self.chips

    def op_count(self, pred=lambda o: True) -> float:
        return sum(1 for ops in self.ops.values() for o in ops
                   if not o.container and pred(o)) / self.chips

    def step_calls(self) -> float:
        """Executions of the step in the window, per chip: each counts by
        the share of its span that lies in the window.  One recorded for
        less than half the median span was cut off where the trace ends,
        and counts by its share of the median."""
        total = 0.0
        for runs in self.modules.values():
            by = collections.Counter()
            for name, a, b in runs:
                by[name] += self._in_window(a, b)
            if not by:
                continue
            step = by.most_common(1)[0][0]
            spans = [(a, b) for name, a, b in runs if name == step and b > a]
            med = statistics.median(b - a for a, b in spans)
            total += sum(self._in_window(a, b)
                         / (b - a if b - a >= med / 2 else med)
                         for a, b in spans)
        return total / self.chips

    def gaps(self, chip: int) -> list[tuple[float, float]]:
        busy = self.busy_intervals(chip)
        edges = [self.t0_ns] + [x for iv in busy for x in iv] + [self.t1_ns]
        return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def label(self, a: float, b: float) -> str:
        cover = collections.Counter()
        for name, s, e in self.host:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[name] += ov
        if not cover:
            return "other"
        name, ov = cover.most_common(1)[0]
        return name if ov >= 0.5 * (b - a) else "other"

    def idle_by_span(self) -> list[tuple[str, float]]:
        by = collections.Counter()
        for c in self.ops or {0: []}:
            for a, b in self.gaps(c):
                by[self.label(a, b)] += (b - a) * 1e-9 / self.chips
        return by.most_common()

    def ops_by_name(self) -> list[tuple[str, float]]:
        by = collections.Counter()
        for ops in self.ops.values():
            for o in ops:
                if not o.container:
                    by[o.name] += (self._in_window(o.start_ns, o.end_ns)
                                   * 1e-9 / self.chips)
        return by.most_common()

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops_by_name()[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.idle_by_span()[:TOP]]}


def _describe(name: str, text: str, instrs: dict) -> dict:
    """An op's name, text, instruction and whether it is a container, from
    the event's name and the text of its stats."""
    head = op_classes.HEAD.match(name)
    if head is None:
        key, short = name.lstrip("%"), name
    else:               # the op's whole HLO line
        key, short = head.group(1), op_classes.label(name)
        text = f"{name} {text}".strip()
    instr = instrs.get(key)
    ins = instr or op_classes.parse(text)
    return {"name": short, "hlo": text, "instr": instr,
            "container": ins is not None and ins.opcode in CONTAINERS}


def reduce_profile(pd, instrs: dict | None = None,
                   spans: set[str] = frozenset()) -> Reduction:
    """A :class:`Reduction` of a ``jax.profiler.ProfileData``; ``instrs``
    maps instruction names of the compiled step to what ``ops.index``
    made of them, and ``spans`` names the harness's host spans."""
    instrs = instrs or {}
    window = None
    host = []
    ops: dict[int, list[Op]] = {}
    modules: dict[int, list[tuple[str, float, float]]] = {}
    known: dict[tuple[str, str], dict] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.end_ns)
                    elif e.name in spans:
                        host.append((e.name, e.start_ns, e.end_ns))
        elif m is not None:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[chip] = [(e.name, e.start_ns, e.end_ns)
                                     for e in line.events]
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    st = _stats(e)
                    text = " ".join(str(st[k]) for k in TEXT_STATS
                                    if k in st)
                    if (e.name, text) not in known:
                        known[e.name, text] = _describe(e.name, text, instrs)
                    ops.setdefault(chip, []).append(Op(
                        start_ns=e.start_ns, end_ns=e.end_ns,
                        **known[e.name, text]))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span on "
                         f"{HOST_PLANE}")
    t0, t1 = window
    ops = {c: [o for o in v if o.end_ns > t0 and o.start_ns < t1]
           for c, v in ops.items()}
    host = [(n, a, b) for n, a, b in host if b > t0 and a < t1]
    modules = {c: [r for r in v if r[2] > t0 and r[1] < t1]
               for c, v in modules.items()}
    return Reduction(t0_ns=t0, t1_ns=t1, ops=ops, host=host, modules=modules)


def reduce_file(path, instrs: dict | None = None,
                spans: set[str] = frozenset()) -> Reduction:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(str(path)),
                          instrs, spans)
