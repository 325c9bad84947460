"""BFP8 quant / dequant Pallas kernels — the paper's §V-A block-floating-
point format as the on-device eviction codec.

Evicted streams (KV pages, skip activations, fragmented weight panels) pass
through these before crossing the HBM<->host boundary: 16-bit words become
8-bit mantissas + one shared exponent per ``block`` values, the fixed
compile-time ratio ``(8 + 8/block)/16`` the DSE's Eq. 2/4 uses.

Tiling: one grid step processes a (rows_per_step, C) stripe held in VMEM;
the block reduction (amax -> exponent) is a masked lane reduction per block,
so every value keeps its 2-D (rows, lanes) layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lane_groups(shape, block: int):
    """Codec block index of every lane of a 2-D stripe: ``(R, C)`` int32.

    The codec works on whole ``(rows, C)`` stripes and never splits the lane
    axis into ``(C // block, block)``: Mosaic refuses that reshape.  Each
    block is instead selected with a lane mask built from this index."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // block


def bfp8_quant_values(x, *, block: int):
    """Value-level quantisation math: (R, C) f32 -> (int8 mantissas (R, C),
    int8 shared exponents (R, C//block)).

    The single source of truth for the codec's numerics — the stripe
    kernels below and the fused streaming_conv ingress/egress kernels all
    call this, so a fused boundary codec cannot drift from the standalone
    ``bfp8_quant``/``bfp8_dequant`` pair by construction.  Per block: a
    masked lane max gives the block's ``amax``, the exponent is computed on
    that ``(R, 1)`` column, and a masked select spreads the block's scale
    back over its lanes — the same arithmetic on the same values as the
    reshaped formulation in ``kernels.ref.bfp8_quant_ref``, bit for bit."""
    x = x.astype(jnp.float32)                           # (R, C)
    R, C = x.shape
    G = C // block
    ax = jnp.abs(x)
    lane_group = _lane_groups((R, C), block)
    exp_col = jax.lax.broadcasted_iota(jnp.int32, (R, G), 1)
    scale = jnp.zeros_like(x)
    exp = jnp.zeros((R, G), jnp.float32)
    for g in range(G):
        in_g = lane_group == g
        amax = jnp.max(jnp.where(in_g, ax, 0.0), axis=1, keepdims=True)
        e = jnp.where(amax > 0,
                      jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-38))), 0.0)
        scale = jnp.where(in_g, jnp.exp2(e - 6.0), scale)
        exp = jnp.where(exp_col == g, e, exp)
    man = jnp.clip(jnp.round(x / scale), -127, 127)
    return man.astype(jnp.int8), exp.astype(jnp.int8)


def bfp8_dequant_values(man, exp, *, block: int, dtype=jnp.float32):
    """Value-level dequantisation math (inverse layout of
    :func:`bfp8_quant_values`): each block's exponent is picked out of its
    column by a masked lane sum (one non-zero term, so exact) and spread
    over the block's lanes by a masked select."""
    man = man.astype(jnp.float32)
    R, C = man.shape
    G = C // block
    expf = exp.astype(jnp.float32)
    lane_group = _lane_groups((R, C), block)
    exp_col = jax.lax.broadcasted_iota(jnp.int32, (R, G), 1)
    scale = jnp.zeros_like(man)
    for g in range(G):
        e = jnp.sum(jnp.where(exp_col == g, expf, 0.0), axis=1, keepdims=True)
        scale = jnp.where(lane_group == g, jnp.exp2(e - 6.0), scale)
    return (man * scale).astype(dtype)


def _quant_kernel(x_ref, man_ref, exp_ref, *, block: int):
    man_ref[...], exp_ref[...] = bfp8_quant_values(x_ref[...], block=block)


def _dequant_kernel(man_ref, exp_ref, o_ref, *, block: int):
    o_ref[...] = bfp8_dequant_values(man_ref[...], exp_ref[...], block=block,
                                     dtype=o_ref.dtype)


def _stripe_rows(R: int, rows: int) -> tuple[int, int]:
    """(rows per grid step, padded row count): stripes of ``rows`` rows
    (a multiple of 8), or one stripe of the whole axis when it is shorter.
    The codec is row-local, so zero rows padded on and sliced off again
    cannot change a real row."""
    rows = min(rows, R)
    return rows, -(-R // rows) * rows


def _pad_rows(x: jax.Array, mp: int) -> jax.Array:
    """Zero-pad the row axis of a 2-D stripe to ``mp`` rows."""
    m = x.shape[0]
    return x if m == mp else jnp.pad(x, ((0, mp - m), (0, 0)))


def bfp8_quant(x: jax.Array, *, block: int = 32, rows: int = 256,
               interpret: bool = False):
    """x: (R, C), C % block == 0 -> (mantissa int8 (R,C), exponent int8
    (R, C/block))."""
    R, C = x.shape
    assert C % block == 0, (x.shape, block)
    rows, Rp = _stripe_rows(R, rows)
    man, exp = pl.pallas_call(
        functools.partial(_quant_kernel, block=block),
        grid=(Rp // rows,),
        in_specs=[pl.BlockSpec((rows, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, C), lambda i: (i, 0)),
                   pl.BlockSpec((rows, C // block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Rp, C), jnp.int8),
                   jax.ShapeDtypeStruct((Rp, C // block), jnp.int8)],
        interpret=interpret, name="smof_bfp8_quant",
    )(_pad_rows(x, Rp))
    return man[:R], exp[:R]


def bfp8_dequant(man: jax.Array, exp: jax.Array, *, block: int = 32,
                 rows: int = 256, dtype=jnp.float32,
                 interpret: bool = False) -> jax.Array:
    R, C = man.shape
    rows, Rp = _stripe_rows(R, rows)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, block=block),
        grid=(Rp // rows,),
        in_specs=[pl.BlockSpec((rows, C), lambda i: (i, 0)),
                  pl.BlockSpec((rows, C // block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), dtype),
        interpret=interpret, name="smof_bfp8_dequant",
    )(_pad_rows(man, Rp), _pad_rows(exp, Rp))
    return out[:R]
