"""Jitted public wrappers for the Pallas kernels, plus the kernel
registry the executors dispatch through.

``interpret`` defaults to True when no TPU is present so the same call
sites run on CPU (kernel bodies executed in Python) and compile to Mosaic
on real hardware.  The resolution lives in exactly one place
(:func:`resolve_interpret`): the executors resolve a ``CompileSpec``'s
``interpret`` once at lowering time and thread the concrete bool down, so
a façade-compiled artifact replays with the same kernel path it was saved
with instead of re-deciding per wrapper call.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .bfp8 import bfp8_dequant, bfp8_quant
from .streamed_matmul import (streamed_matmul, streamed_matmul_padded,
                              vmem_bytes)
from . import ref
from . import streaming_conv


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """The one shared ``interpret`` resolution: an explicit flag (e.g. a
    saved ``CompileSpec.interpret``) wins; ``None`` falls back to
    interpret-on-CPU.  Every wrapper and both executors route through
    here, so the backend decision cannot diverge between call sites."""
    return (not _on_tpu()) if interpret is None else bool(interpret)


# =============================================================================
# Kernel registry: op kind -> {reference, pallas} bodies
# =============================================================================

@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One lowerable op kind's dispatch row.

    ``pallas=None`` means the kind has no Pallas body (data movement /
    variadic ops) and the reference body runs in every kernel mode —
    ``kernel_for`` reports which body was actually selected.
    ``fuse_bfp8`` marks kinds whose Pallas body can fuse the BFP8
    boundary codec (ingress ``payload=`` / egress ``encode=True``).
    """
    kind: str
    reference: Callable
    pallas: Callable | None = None
    fuse_bfp8: bool = False


KERNEL_REGISTRY: dict[str, KernelEntry] = {}


def _register(entry: KernelEntry) -> None:
    KERNEL_REGISTRY[entry.kind] = entry


for _kind in ("conv", "matmul", "deconv"):
    _register(KernelEntry(kind=_kind, reference=ref.conv2d_ref,
                          pallas=streaming_conv.conv2d, fuse_bfp8=True))
_register(KernelEntry(kind="dwconv", reference=ref.dwconv_ref,
                      pallas=streaming_conv.dwconv, fuse_bfp8=True))
_register(KernelEntry(kind="pool", reference=ref.pool_ref,
                      pallas=streaming_conv.pool, fuse_bfp8=True))
_register(KernelEntry(kind="act", reference=ref.act_relu_ref,
                      pallas=streaming_conv.act_relu, fuse_bfp8=True))
# data-movement / variadic kinds: reference body in every mode
for _kind in ("input", "upsample", "add", "mul", "concat", "output"):
    _register(KernelEntry(kind=_kind, reference=lambda *a, **k: None))


def kernel_for(kind: str, *, use_pallas: bool
               ) -> tuple[Callable | None, bool]:
    """(body, is_pallas) for one op kind under the resolved kernel mode.
    Kinds with no Pallas body fall back to their reference body (and
    ``is_pallas`` is False) — the conformance matrix sweeps them anyway
    to lock the fallback's parity."""
    entry = KERNEL_REGISTRY.get(kind)
    if entry is None:
        return None, False
    if use_pallas and entry.pallas is not None:
        return entry.pallas, True
    return entry.reference, False


def fusable_kinds() -> tuple[str, ...]:
    """Op kinds whose Pallas body fuses the BFP8 boundary codec."""
    return tuple(k for k, e in KERNEL_REGISTRY.items() if e.fuse_bfp8)


def lowerable_kinds() -> tuple[str, ...]:
    return tuple(KERNEL_REGISTRY)


# =============================================================================
# Jitted public wrappers
# =============================================================================

@functools.partial(jax.jit, static_argnames=("static_fraction", "bm", "bk",
                                             "bn", "interpret"))
def fragmented_matmul(x: jax.Array, w: jax.Array, *,
                      static_fraction: float = 0.5, bm: int = 128,
                      bk: int = 128, bn: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """y = x @ w with the leading ``static_fraction`` of w's rows pinned in
    VMEM (the paper's 1 - m) and the rest streamed — the public form of the
    weight-fragmentation kernel, splitting w at a 128-aligned row."""
    K = w.shape[0]
    ks = max(int(round(static_fraction * K / 128.0)) * 128, 0)
    ks = min(ks, K - 128) if K > 128 else 0
    interpret = resolve_interpret(interpret)
    if ks <= 0:
        return streamed_matmul(x, w[:128], w[128:], bm=bm, bk=bk, bn=bn,
                               interpret=interpret) if K > 128 else \
            jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    return streamed_matmul(x, w[:ks], w[ks:], bm=bm, bk=bk, bn=bn,
                           interpret=interpret)


def evict_encode(x: jax.Array, *, block: int = 32, interpret=None):
    """Quantise an eviction stream to BFP8 before it leaves HBM."""
    return bfp8_quant(x, block=block, interpret=resolve_interpret(interpret))


def evict_decode(man, exp, *, block: int = 32, dtype=jnp.float32,
                 interpret=None):
    return bfp8_dequant(man, exp, block=block, dtype=dtype,
                        interpret=resolve_interpret(interpret))


__all__ = ["fragmented_matmul", "evict_encode", "evict_decode",
           "streamed_matmul", "streamed_matmul_padded", "bfp8_quant",
           "bfp8_dequant", "vmem_bytes", "ref",
           "resolve_interpret", "KernelEntry", "KERNEL_REGISTRY",
           "kernel_for", "fusable_kinds", "lowerable_kinds",
           "streaming_conv"]
