"""Which part of the program each device op of a trace belongs to, from the
scopes the program names its ops by.

The program wraps what it traces in ``jax.named_scope``s that start with
``smof.`` (``repro.obs.trace.scope``): ``smof.<kind>:<vertex>`` for a
vertex, ``smof.codec.enc:<vertex>``, ``smof.codec.dec:<src>-<dst>``,
``smof.tick`` with ``smof.tick.read`` and ``smof.tick.carry`` inside it,
and ``smof.emit``.  The compiled step's HLO carries them in each
instruction's ``metadata={op_name="jit(step)/smof.emit/while/body/
closed_call/smof.tick/smof.conv:conv_14/smof_conv/pallas_call"}``, which
``ops.index`` keeps in ``Instr.text``.  Scopes nest (``smof.emit`` spans
the whole scan, ``smof.tick`` its body), so an op belongs to its innermost
one, and only the scan's own stacking of the output has ``smof.emit``
innermost.  A fusion carries the ``op_name`` of its
root instruction: where XLA fuses work of two scopes, the op counts for the
scope of its root.  The last component of an ``op_name`` is the JAX
primitive of that root (``slice``, ``pad``, ``broadcast_in_dim``, ...).
The hop's copies, XLA's copies between HBM and VMEM and the loops XLA
makes of its own carry no ``op_name`` at all.

``classify`` puts each op in one class, so that the classes partition the
device's busy time: ``hop``, ``conv`` and ``codec`` as ``bench/ops.py``
finds them (the readers of ``hop_ms``, ``conv_roofline`` and ``bfp8_ms``),
then ``emit`` (output assembly), ``eltwise`` (kernels and fusions of the
element-wise vertices that move no layout), ``glue`` (layout ops anywhere
but in ``smof.emit``, and XLA's copies between HBM and VMEM, which carry
no scope) and ``other``.  ``python -m bench.scopes <trace.xplane.pb>
<step.hlo.txt> <B>`` prints the partition of a kept trace.
"""
from __future__ import annotations

import collections
import functools
import re

from bench import ops

PREFIX = "smof."
OP_NAME = re.compile(r'op_name="([^"]*)"')
ELTWISE_KINDS = ("act", "pool", "upsample", "add", "mul")
# layout ops: XLA opcodes of bare instructions, and the JAX primitives an
# op_name ends with, for fusions rooted at one
LAYOUT_OPCODES = ("pad", "slice", "dynamic-slice", "reshape", "broadcast",
                  "concatenate", "convert", "bitcast", "transpose",
                  "copy", "copy-start", "copy-done")
LAYOUT_PRIMITIVES = ("pad", "slice", "dynamic_slice", "reshape",
                     "broadcast_in_dim", "concatenate", "convert_element_type",
                     "copy", "squeeze", "expand_dims", "transpose")
CLASSES = ("hop", "conv", "codec", "emit", "eltwise", "glue", "other")


@functools.lru_cache(maxsize=None)
def _op_name(text: str) -> str | None:
    m = OP_NAME.search(text)
    return m.group(1) if m else None


def op_name(op) -> str | None:
    """The ``op_name`` of a trace op's instruction, where it has one."""
    ins = ops.instr(op)
    return None if ins is None else _op_name(ins.text)


def scope(op) -> str | None:
    """The innermost ``smof.`` scope of a trace op, e.g.
    ``smof.conv:conv_14``, or None."""
    name = op_name(op)
    if name is None:
        return None
    found = [c for c in name.split("/") if c.startswith(PREFIX)]
    return found[-1] if found else None


def kind(op) -> str | None:
    """The kind of an op's scope: ``conv`` of ``smof.conv:conv_14``,
    ``codec.dec`` of ``smof.codec.dec:act_9-concat_23``, ``emit``."""
    s = scope(op)
    return None if s is None else s[len(PREFIX):].split(":", 1)[0]


def is_layout(op) -> bool:
    """A pad, slice, reshape, broadcast, concatenate, convert or copy,
    alone or as the root of a fusion; never a host copy."""
    ins = ops.instr(op)
    if ins is None or ins.host_copy:
        return False
    if ins.opcode == "fusion":
        name = op_name(op)
        return name is not None and name.split("/")[-1] in LAYOUT_PRIMITIVES
    return ins.opcode in LAYOUT_OPCODES


def classify(op) -> str:
    """The one class of ``CLASSES`` an op belongs to."""
    if ops.is_hop(op):
        return "hop"
    if ops.is_conv(op):
        return "conv"
    if ops.is_bfp8(op):
        return "codec"
    k = kind(op)
    if k == "emit":
        return "emit"
    layout = is_layout(op)
    if k in ELTWISE_KINDS and not layout:
        return "eltwise"
    return "glue" if layout else "other"


def named(trace) -> bool:
    """Whether the program named any op of the traced window."""
    return any(scope(o) is not None for v in trace.ops.values() for o in v
               if not o.container)


def ms_per_frame(m, cls: str) -> float | None:
    """Device ms per frame of the ops of class ``cls``; None where the
    program named no op (a program without scopes) or there are none."""
    if m.frames <= 0 or not named(m.trace):
        return None
    t = m.trace.op_seconds(lambda o: classify(o) == cls)
    return 1e3 * t / m.frames if t > 0 else None


def partition(trace, frames: float, top: int = 3) -> dict:
    """Each class's device ms per frame and its ``top`` scopes by time
    (``(none)`` for ops with no scope): ``{class: (ms, [(scope, ms)])}``."""
    by = {c: collections.Counter() for c in CLASSES}
    for v in trace.ops.values():
        for o in v:
            if o.container:
                continue
            t = min(o.end_ns, trace.t1_ns) - max(o.start_ns, trace.t0_ns)
            by[classify(o)][scope(o) or "(none)"] += \
                1e-6 * max(t, 0.0) / trace.chips / frames
    return {c: (sum(cnt.values()), cnt.most_common(top))
            for c, cnt in by.items()}


def main(argv: list[str] | None = None) -> int:
    import sys

    from bench import trace_reduce
    xplane, hlo, batch = (argv if argv is not None else sys.argv[1:])
    with open(hlo) as f:
        red = trace_reduce.reduce_file(xplane, ops.index(f.read()))
    frames = red.step_calls() * int(batch)
    busy = 1e3 * red.busy_s / frames
    print(f"{frames:.3f} frames, busy {busy:.4f} ms/frame")
    for cls, (ms, tops) in partition(red, frames, top=6).items():
        print(f"{cls:8s} {ms:8.4f} ms/frame  "
              + ", ".join(f"{s} {t:.4f}" for s, t in tops))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
