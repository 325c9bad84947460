"""Which device operations of a trace implement which work.

A TPU trace names each operation by its whole HLO line, layouts and
attributes included.  The harness finds the instruction in the compiled
step's HLO text (``index``) by the name the line begins with and, where it
has none, parses the line itself.  From the instruction:

- Pallas kernels are ``tpu_custom_call`` custom calls, told apart by their
  operand and result types: a conv (``y = x @ W``, whole or with the
  weight and input split into fragments, or with a BFP8 payload coming in)
  takes two or more operands, one of them float; the standalone BFP8
  dequant takes int8 operands only; the standalone quant takes one float
  operand and returns int8 only.  Element-wise and pool kernels take one
  operand.
- XLA's own matmuls, which stand in for convs with K <= 128, are output
  fusions (``kind=kOutput``: on a TPU, a dot or convolution with what XLA
  fused around it), or bare convolutions and dots.
- The off-chip hop is the asynchronous copies to and from host memory
  (memory space ``S(5)``): each copy-start or copy-done whose shapes name
  it, and each copy-done of such a start.  Copies between HBM and VMEM are
  not the hop.

``label`` shortens an op's line for a breakdown, and ``hbm_bytes`` reads
from it what the op's arrays in HBM hold.
"""
from __future__ import annotations

import dataclasses
import re

HEAD = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
DTYPE = re.compile(r"\b(pred|s8|u8|s16|s32|u32|bf16|f16|f32|f64)\[")
CONSTRAINTS = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
FLOATS = ("bf16", "f16", "f32", "f64")
INTS = ("s8", "u8")
HOST_SPACE = "S(5)"
LAYOUT = re.compile(r"\{[^{}]*\}")
ARRAY = re.compile(r"\b(pred|s8|u8|s16|s32|u32|bf16|f16|f32|f64)"
                   r"\[([\d,]*)\](\{[^{}]*\})?")
ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "bf16": 2, "f16": 2,
              "s32": 4, "u32": 4, "f32": 4, "f64": 8}
VMEM_SPACE = "S(1)"
OPERAND_NAME = re.compile(r" ?%[\w.\-]+")


@dataclasses.dataclass(frozen=True)
class Instr:
    name: str
    opcode: str
    results: tuple[str, ...]      # dtypes of the result, in order
    operands: tuple[str, ...]     # dtypes of the operands, where known
    text: str
    host_copy: bool = False       # a copy to or from host memory


def _closing(s: str, i: int) -> int:
    """The index just past the parenthesis that closes the one at ``s[i]``,
    or -1 where it is not closed."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] == "(":
            depth += 1
        elif s[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return -1


def _split(line: str):
    """``(name, opcode, result, operands)`` of an HLO line, the last two as
    the text of the result's type and of the operand list; or None."""
    head = HEAD.match(line)
    if head is None:
        return None
    rest = line[head.end():]
    op = OPCODE.search(rest)
    if op is None:
        return None
    end = _closing(rest, op.end() - 1)
    return (head.group(1), op.group(1), rest[:op.start() + 1],
            rest[op.end():end - 1] if end > 0 else "")


def parse(line: str) -> Instr | None:
    """The instruction of one HLO line, or None where it is not one."""
    parts = _split(line)
    if parts is None:
        return None
    name, opcode, result, args = parts
    cons = CONSTRAINTS.search(line)
    # operand types from the layout constraints, else printed inline, as a
    # trace's own text gives them
    operands = tuple(DTYPE.findall(cons.group(1) if cons else args))
    return Instr(name=name, opcode=opcode, results=tuple(DTYPE.findall(result)),
                 operands=operands, text=line.strip(),
                 host_copy=(opcode in ("copy-start", "copy-done")
                            and HOST_SPACE in result + args))


def label(line: str) -> str:
    """An HLO line as a short name: the instruction's name, result, opcode
    and operand types, without layouts, operand names or attributes."""
    if parse(line) is None:
        return line
    short = line.strip()
    while True:
        bare = LAYOUT.sub("", short)
        if bare == short:
            break
        short = bare
    op = OPCODE.search(short)
    end = _closing(short, op.end() - 1)
    if end < 0:
        return short
    return short[:op.end()] + OPERAND_NAME.sub("", short[op.end():end]).strip()


def hbm_bytes(op) -> int | None:
    """Bytes of the op's result and operands that lie in HBM, from the
    shapes and memory spaces of the trace's own line: an array whose layout
    names VMEM (``S(1)``) is left out.  None where the line does not give
    the operands' types."""
    parts = _split(op.hlo or "")
    if parts is None or not ARRAY.search(parts[3]):
        return None
    total = 0
    for dtype, dims, layout in ARRAY.findall(parts[2] + parts[3]):
        if VMEM_SPACE not in layout:
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += n * ITEM_BYTES[dtype]
    return total


def index(hlo_text: str) -> dict[str, Instr]:
    """Every instruction of a compiled module's HLO text that runs as an
    operation of its own, by name: those inside a fusion's computation do
    not.  A copy-done is a host copy where its copy-start is."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo_text))
    out: dict[str, Instr] = {}
    inside = False
    for line in hlo_text.splitlines():
        comp = COMPUTATION.match(line)
        if comp is not None:
            inside = comp.group(1) in fused
            continue
        ins = None if inside else parse(line)
        if ins is not None:
            out[ins.name] = ins
    for name, ins in out.items():
        if ins.opcode == "copy-done":
            src = re.search(r"copy-done\(%([\w.\-]+)\)", ins.text)
            start = out.get(src.group(1)) if src else None
            if (start is not None and start.host_copy) \
                    or HOST_SPACE in ins.text.split(" copy-done(")[0]:
                out[name] = dataclasses.replace(ins, host_copy=True)
    return out


def instr(op) -> Instr | None:
    """The instruction of a trace op: from the compiled text where the
    harness gave it, else parsed from the trace's own text."""
    if getattr(op, "instr", None) is not None:
        return op.instr
    return parse(op.hlo) if op.hlo else None


def is_pallas(ins: Instr) -> bool:
    return ins.opcode == "custom-call" and "tpu_custom_call" in ins.text


def is_conv(op) -> bool:
    ins = instr(op)
    if ins is None:
        return False
    if is_pallas(ins):
        return (len(ins.operands) >= 2
                and any(t in FLOATS for t in ins.operands))
    if ins.opcode == "fusion":
        return "kind=kOutput" in ins.text
    return ins.opcode in ("convolution", "dot")


def is_bfp8(op) -> bool:
    ins = instr(op)
    if ins is None or not is_pallas(ins) or not ins.operands:
        return False
    dequant = all(t in INTS for t in ins.operands)
    quant = (len(ins.operands) == 1 and ins.operands[0] in FLOATS
             and all(t in INTS for t in ins.results))
    return dequant or quant


def is_hop(op) -> bool:
    ins = instr(op)
    return ins is not None and ins.host_copy
