"""Compiled HLO text reduced to the instructions the device runs, for tests
that compare two compiles which should differ only in names."""
from __future__ import annotations

import base64
import re

# what a compiled module's text holds besides its instructions
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_METADATA_RE = re.compile(r", metadata=\{[^{}]*\}")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_KERNEL_BODY_RE = re.compile(r'("custom_call_config":\{"body":")([^"]*)(")')
_KERNEL_MODULE_RE = re.compile(r"^module @[\w.\-]+", re.M)


def _kernel_asm(body: str) -> str:
    """A Pallas kernel's serialized Mosaic module as text, without its
    debug locations and with its module (kernel) name left out."""
    from jaxlib.mlir import ir
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body))
        asm = module.operation.get_asm(enable_debug_info=False)
    return _KERNEL_MODULE_RE.sub("module @kernel", asm)


def instructions_only(hlo: str) -> str:
    """A compiled module's text as the instructions the device runs: the
    stack-frame tables and every ``metadata={...}`` (``op_name`` and source
    lines, which XLA does not read when it optimises) dropped, each name
    replaced by its order of first appearance, and each Pallas kernel's
    body decoded without its debug locations or kernel name.  Two modules
    that differ only in scopes and kernel names give the same text."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in _DEBUG_TABLES:
            skip = True
            continue
        if skip and (line.startswith("%") or line.startswith("ENTRY")):
            skip = False
        if not skip:
            out.append(line)
    text = _METADATA_RE.sub("", "\n".join(out))
    names: dict[str, str] = {}
    text = _NAME_RE.sub(
        lambda m: "%" + names.setdefault(m.group(1), f"i{len(names)}"), text)
    return _KERNEL_BODY_RE.sub(
        lambda m: m.group(1) + _kernel_asm(m.group(2)).replace('"', "'")
        + m.group(3), text)
