"""Streaming conv / dwconv / pool Pallas kernels with a fused BFP8
boundary codec — the kernel-level analogue of the paper's line-buffer
dataflow (§III) for the executable graphs' op vocabulary.

Layout (docs/KERNELS.md has the full picture):

* every kernel walks a **row-block grid**: one grid step owns a
  ``(bm, C)`` stripe of positions, the software form of a line buffer
  that consumes a sliding window of rows per cycle.  The channel-mixing
  ops (``conv``/``matmul``/``deconv``) additionally tile the *output*
  channel axis by ``bc`` with the **full K axis per grid step** — a
  single ``jnp.dot`` per tile, no K-split accumulation, so a tile never
  changes which products an output sums.  The order of that sum is the
  backend dot's: XLA's CPU dot picks it by the operands' shapes, so off a
  TPU two tilings may differ by reassociation, a few ulps
  (``tests/test_properties.py`` holds them to that bound).
* **fused ingress**: when the op's input edge arrives BFP8-evicted, the
  kernel takes the spill payload (int8 mantissas + per-block int8 shared
  exponents) and dequantises per block *inside* the ``pallas_call``
  (``bfp8.bfp8_dequant_values``) instead of round-tripping through a
  separate ``bfp8_dequant`` dispatch.
* **fused egress**: when the op's output edge is BFP8-evicted, the same
  ``pallas_call`` emits the f32 activation *and* its quantised spill
  payload (multi-output ``out_specs``).  Quantisation blocks are
  row-local ``(1, block)`` runs along the channel axis, so egress fusion
  pins the full (block-padded) channel width per row-block — ``bm``
  still tiles, ``bc`` does not apply — and the payload is bitwise the
  one ``runtime.executor.bfp8_spill_encode`` would produce.

Padding rules: rows pad with zeros to the row-block multiple (padded
rows are computed and sliced away — zero rows cannot perturb real rows
since nothing reduces over the position axis except ``pool``, whose
grid is aligned to whole output rows).  Egress channel padding matches
``bfp8_spill_encode`` exactly: pad to ``round_up(c, block)`` with
zeros, quantise the padded stripe.

Everything here is numerics-only: traffic accounting stays in
``runtime.executor`` / the DSE.  ``interpret`` is resolved by the
caller (``kernels.ops.resolve_interpret`` / the executors) — these
wrappers take a concrete bool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bfp8 import _pad_rows, bfp8_dequant_values, bfp8_quant_values
from .streamed_matmul import _round_up

DEFAULT_BM = 128            # row-block default (positions per grid step)
DEFAULT_BC = 128            # out-channel-block default (conv family only)
BM_ALIGN = 8                # a row block is whole sublane tiles ...
BC_ALIGN = 128              # ... and a channel block whole lane tiles

# Module-level codec indirection: the fused kernels look these up at trace
# time, so the differential fuzzer's fault injector can skew the *fused*
# codec specifically (testing.oracle FAULTS) without touching the
# standalone bfp8 stripe kernels.
_quant_vals = bfp8_quant_values
_dequant_vals = bfp8_dequant_values


def _tile(n: int, b: int, default: int, align: int) -> int:
    """Resolve a tile size Mosaic accepts: 0 means the default; a request
    rounds up to a multiple of ``align`` (8 rows / 128 lanes), and a tile
    that covers the axis becomes the full axis."""
    b = _round_up(b if b > 0 else default, align)
    return min(b, n) if n > 0 else b


def _name(kind: str, payload, encode: bool) -> str:
    """The ``pallas_call`` name of a kernel: its kind and the codec it
    fuses, e.g. ``smof_conv``, ``smof_act_bfp8out`` or
    ``smof_pool_bfp8in_bfp8out``; the compiled custom call carries it."""
    return (f"smof_{kind}" + ("_bfp8in" if payload is not None else "")
            + ("_bfp8out" if encode else ""))


def _pad_payload(payload, mp: int):
    man, exp = payload
    return _pad_rows(man, mp), _pad_rows(exp, mp)


# =============================================================================
# conv / matmul / deconv — 1x1 channel mixing, y = x @ w
# =============================================================================

def _conv_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32)


def _conv_dec_kernel(man_ref, exp_ref, w_ref, o_ref, *, block, cin):
    x = _dequant_vals(man_ref[...], exp_ref[...], block=block)[:, :cin]
    o_ref[...] = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)


def _conv_enc_kernel(x_ref, w_ref, o_ref, man_ref, exp_ref, *, block):
    y = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = y
    man_ref[...], exp_ref[...] = _quant_vals(y, block=block)


def _conv_dec_enc_kernel(man_ref, exp_ref, w_ref, o_ref, yman_ref, yexp_ref,
                         *, block, cin):
    x = _dequant_vals(man_ref[...], exp_ref[...], block=block)[:, :cin]
    y = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = y
    yman_ref[...], yexp_ref[...] = _quant_vals(y, block=block)


def conv2d(x, w, *, payload=None, encode=False, block: int = 32,
           bm: int = 0, bc: int = 0, interpret: bool = False):
    """Tiled streaming 1x1 conv: ``y = x @ w`` over a row-block grid.

    x: (m, cin) f32 — or pass ``payload=(man, exp)`` (int8 spill buffers,
    channel axis padded to the codec block) for a BFP8-evicted input edge;
    the per-block dequant then runs inside the kernel.  ``encode=True``
    additionally emits the output's BFP8 spill payload from the same
    ``pallas_call`` and returns ``(y, (man, exp))``.

    Contract: ``y`` is ``jnp.dot(x, w)`` (with ``x`` the dequantised
    input where applicable), each output the same full-K sum for every
    ``bm``/``bc`` (its order is the backend dot's: module doc), and the
    egress payload is bitwise ``bfp8_quant`` of the block-padded ``y``
    the kernel emits.
    """
    cin, n = w.shape
    if payload is not None:
        man, exp = payload
        m, c_pad = man.shape
        assert c_pad == _round_up(cin, block), (man.shape, cin, block)
    else:
        m = x.shape[0]
        assert x.shape[1] == cin, (x.shape, w.shape)
    bm = _tile(m, bm, DEFAULT_BM, BM_ALIGN)
    mp = _round_up(m, bm)

    if not encode:
        bc = _tile(n, bc, DEFAULT_BC, BC_ALIGN)
        npad = _round_up(n, bc)
        wp = jnp.pad(w, ((0, 0), (0, npad - n)))
        grid = (mp // bm, npad // bc)
        if payload is None:
            y = pl.pallas_call(
                _conv_kernel, grid=grid,
                in_specs=[pl.BlockSpec((bm, cin), lambda i, j: (i, 0)),
                          pl.BlockSpec((cin, bc), lambda i, j: (0, j))],
                out_specs=pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct((mp, npad), jnp.float32),
                interpret=interpret, name=_name("conv", payload, encode),
            )(_pad_rows(x, mp), wp)
        else:
            y = pl.pallas_call(
                functools.partial(_conv_dec_kernel, block=block, cin=cin),
                grid=grid,
                in_specs=[pl.BlockSpec((bm, c_pad), lambda i, j: (i, 0)),
                          pl.BlockSpec((bm, c_pad // block),
                                       lambda i, j: (i, 0)),
                          pl.BlockSpec((cin, bc), lambda i, j: (0, j))],
                out_specs=pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct((mp, npad), jnp.float32),
                interpret=interpret, name=_name("conv", payload, encode),
            )(*_pad_payload(payload, mp), wp)
        return y[:m, :n]

    # egress fusion: full (block-padded) channel width per row-block so the
    # row-local quant blocks line up with bfp8_spill_encode's padding
    npad = _round_up(n, block)
    wp = jnp.pad(w, ((0, 0), (0, npad - n)))
    out_specs = [pl.BlockSpec((bm, npad), lambda i: (i, 0)),
                 pl.BlockSpec((bm, npad), lambda i: (i, 0)),
                 pl.BlockSpec((bm, npad // block), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((mp, npad), jnp.float32),
                 jax.ShapeDtypeStruct((mp, npad), jnp.int8),
                 jax.ShapeDtypeStruct((mp, npad // block), jnp.int8)]
    if payload is None:
        y, man_o, exp_o = pl.pallas_call(
            functools.partial(_conv_enc_kernel, block=block),
            grid=(mp // bm,),
            in_specs=[pl.BlockSpec((bm, cin), lambda i: (i, 0)),
                      pl.BlockSpec((cin, npad), lambda i: (0, 0))],
            out_specs=out_specs, out_shape=out_shape, interpret=interpret,
            name=_name("conv", payload, encode),
        )(_pad_rows(x, mp), wp)
    else:
        y, man_o, exp_o = pl.pallas_call(
            functools.partial(_conv_dec_enc_kernel, block=block, cin=cin),
            grid=(mp // bm,),
            in_specs=[pl.BlockSpec((bm, c_pad), lambda i: (i, 0)),
                      pl.BlockSpec((bm, c_pad // block), lambda i: (i, 0)),
                      pl.BlockSpec((cin, npad), lambda i: (0, 0))],
            out_specs=out_specs, out_shape=out_shape, interpret=interpret,
            name=_name("conv", payload, encode),
        )(*_pad_payload(payload, mp), wp)
    return y[:m, :n], (man_o[:m], exp_o[:m])


# =============================================================================
# dwconv — depthwise temporal conv, 'same' padding, halo rows via pl.ds
# =============================================================================

def _dw_mix(w, xs_ref, base, bm, taps):
    """The reference tap sum on a row tile: ``sum`` in the same order as
    ``runtime.executor._dwconv`` so the accumulation is bit-identical.
    The taps are overlapping ``(bm, c)`` windows of the 'same'-padded
    input, read from a ref with ``pl.ds`` (BlockSpecs cannot overlap)."""
    return sum(w[k][None, :] * xs_ref[pl.ds(base + k, bm), :]
               for k in range(taps))


def _dw_decode_window(man_ref, exp_ref, xs_ref, *, block, c, bm, taps):
    """Dequantise this grid step's ``bm + taps - 1`` halo rows of the
    row-padded spill payload into the f32 scratch the taps read.  Zero
    payload rows decode to exact zeros, so the padding is the 'same' pad."""
    rows = pl.ds(pl.program_id(0) * bm, bm + taps - 1)
    xs_ref[...] = _dequant_vals(man_ref[rows, :], exp_ref[rows, :],
                                block=block)[:, :c]


def _dwconv_kernel(xp_ref, w_ref, o_ref, *, bm, taps):
    o_ref[...] = _dw_mix(w_ref[...], xp_ref, pl.program_id(0) * bm, bm,
                         taps)


def _dwconv_dec_kernel(man_ref, exp_ref, w_ref, o_ref, xs_ref, *, block, c,
                       bm, taps):
    _dw_decode_window(man_ref, exp_ref, xs_ref, block=block, c=c, bm=bm,
                      taps=taps)
    o_ref[...] = _dw_mix(w_ref[...], xs_ref, 0, bm, taps)


def _dwconv_enc_kernel(xp_ref, w_ref, o_ref, man_ref, exp_ref, *, block,
                       bm, taps):
    y = _dw_mix(w_ref[...], xp_ref, pl.program_id(0) * bm, bm, taps)
    o_ref[...] = y
    c = y.shape[1]
    yq = jnp.pad(y, ((0, 0), (0, _round_up(c, block) - c)))
    man_ref[...], exp_ref[...] = _quant_vals(yq, block=block)


def _dwconv_dec_enc_kernel(man_ref, exp_ref, w_ref, o_ref, yman_ref,
                           yexp_ref, xs_ref, *, block, c, bm, taps):
    _dw_decode_window(man_ref, exp_ref, xs_ref, block=block, c=c, bm=bm,
                      taps=taps)
    y = _dw_mix(w_ref[...], xs_ref, 0, bm, taps)
    o_ref[...] = y
    yq = jnp.pad(y, ((0, 0), (0, _round_up(c, block) - c)))
    yman_ref[...], yexp_ref[...] = _quant_vals(yq, block=block)


def dwconv(x, w, *, payload=None, encode=False, block: int = 32,
           bm: int = 0, interpret: bool = False):
    """Streaming depthwise temporal conv (w: (taps, c), 'same' padding).

    Row-block grid with a ``taps``-row halo: the input stays un-blocked
    (index map pins it) and each grid step reads its overlapping windows
    with ``pl.ds`` — the line-buffer access pattern.  Fusion flags as in
    :func:`conv2d`; ``payload`` carries ``c`` via ``w.shape[1]``.
    """
    taps, c = w.shape
    if payload is not None:
        man, exp = payload
        m, c_pad = man.shape
        assert c_pad == _round_up(c, block), (man.shape, c, block)
    else:
        m = x.shape[0]
        assert x.shape[1] == c, (x.shape, w.shape)
    bm = _tile(m, bm, DEFAULT_BM, BM_ALIGN)
    mp = _round_up(m, bm)
    pad = taps // 2
    cq = _round_up(c, block)
    grid = (mp // bm,)

    if payload is None:
        xp = jnp.pad(x, ((pad, (taps - 1 - pad) + (mp - m)), (0, 0)))
        in_specs = [pl.BlockSpec(xp.shape, lambda i: (0, 0)),
                    pl.BlockSpec((taps, c), lambda i: (0, 0))]
        if not encode:
            y = pl.pallas_call(
                functools.partial(_dwconv_kernel, bm=bm, taps=taps),
                grid=grid, in_specs=in_specs,
                out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((mp, c), jnp.float32),
                interpret=interpret,
                name=_name("dwconv", payload, encode))(xp, w)
            return y[:m]
        y, man_o, exp_o = pl.pallas_call(
            functools.partial(_dwconv_enc_kernel, block=block, bm=bm,
                              taps=taps),
            grid=grid, in_specs=in_specs,
            out_specs=[pl.BlockSpec((bm, c), lambda i: (i, 0)),
                       pl.BlockSpec((bm, cq), lambda i: (i, 0)),
                       pl.BlockSpec((bm, cq // block), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((mp, c), jnp.float32),
                       jax.ShapeDtypeStruct((mp, cq), jnp.int8),
                       jax.ShapeDtypeStruct((mp, cq // block), jnp.int8)],
            interpret=interpret,
            name=_name("dwconv", payload, encode))(xp, w)
        return y[:m], (man_o[:m], exp_o[:m])

    # ingress-fused: the payload stays un-blocked too (the decode is
    # row-local but the halo needs neighbouring rows).  It is row-padded
    # like ``xp`` above: zero mantissas decode to the zero 'same' pad.
    rows = ((pad, (taps - 1 - pad) + (mp - m)), (0, 0))
    man, exp = jnp.pad(man, rows), jnp.pad(exp, rows)
    in_specs = [pl.BlockSpec(man.shape, lambda i: (0, 0)),
                pl.BlockSpec(exp.shape, lambda i: (0, 0)),
                pl.BlockSpec((taps, c), lambda i: (0, 0))]
    scratch = [pltpu.VMEM((bm + taps - 1, c), jnp.float32)]
    if not encode:
        y = pl.pallas_call(
            functools.partial(_dwconv_dec_kernel, block=block, c=c, bm=bm,
                              taps=taps),
            grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((mp, c), jnp.float32),
            scratch_shapes=scratch, interpret=interpret,
            name=_name("dwconv", payload, encode))(man, exp, w)
        return y[:m]
    y, man_o, exp_o = pl.pallas_call(
        functools.partial(_dwconv_dec_enc_kernel, block=block, c=c, bm=bm,
                          taps=taps),
        grid=grid, in_specs=in_specs, scratch_shapes=scratch,
        out_specs=[pl.BlockSpec((bm, c), lambda i: (i, 0)),
                   pl.BlockSpec((bm, cq), lambda i: (i, 0)),
                   pl.BlockSpec((bm, cq // block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, c), jnp.float32),
                   jax.ShapeDtypeStruct((mp, cq), jnp.int8),
                   jax.ShapeDtypeStruct((mp, cq // block), jnp.int8)],
        interpret=interpret,
        name=_name("dwconv", payload, encode))(man, exp, w)
    return y[:m], (man_o[:m], exp_o[:m])


# =============================================================================
# pool — position-axis mean, grid aligned to whole output rows
# =============================================================================

def _pool_kernel(x_ref, o_ref, *, k):
    x = x_ref[...]
    o_ref[...] = x.reshape(o_ref.shape[0], k, x.shape[1]).mean(axis=1)


def _pool_dec_kernel(man_ref, exp_ref, o_ref, *, block, c, k):
    x = _dequant_vals(man_ref[...], exp_ref[...], block=block)[:, :c]
    o_ref[...] = x.reshape(o_ref.shape[0], k, c).mean(axis=1)


def _pool_enc_kernel(x_ref, o_ref, man_ref, exp_ref, *, block, k):
    x = x_ref[...]
    c = x.shape[1]
    y = x.reshape(o_ref.shape[0], k, c).mean(axis=1)
    o_ref[...] = y
    yq = jnp.pad(y, ((0, 0), (0, _round_up(c, block) - c)))
    man_ref[...], exp_ref[...] = _quant_vals(yq, block=block)


def _pool_dec_enc_kernel(man_ref, exp_ref, o_ref, yman_ref, yexp_ref, *,
                         block, c, k):
    x = _dequant_vals(man_ref[...], exp_ref[...], block=block)[:, :c]
    y = x.reshape(o_ref.shape[0], k, c).mean(axis=1)
    o_ref[...] = y
    yq = jnp.pad(y, ((0, 0), (0, _round_up(c, block) - c)))
    yman_ref[...], yexp_ref[...] = _quant_vals(yq, block=block)


def pool(x, m_out: int, *, c: int | None = None, payload=None, encode=False,
         block: int = 32, bm: int = 0, interpret: bool = False):
    """Streaming mean-pool (m -> m_out rows).  The row-block grid tiles
    *output* rows by ``bm``, each step consuming the aligned ``bm * k``
    input rows (k = m / m_out) — windows never straddle a grid step, so
    tiling cannot reassociate any window's mean.  Fusion flags as in
    :func:`conv2d`; ingress needs ``c`` (the payload is block-padded)."""
    if payload is not None:
        assert c is not None, "pool ingress fusion needs the channel count"
        man, exp = payload
        m, c_pad = man.shape
        assert c_pad == _round_up(c, block), (man.shape, c, block)
    else:
        m, c = x.shape
    if m % m_out:
        raise ValueError(f"pool needs m_out | m, got {m} -> {m_out}")
    k = m // m_out
    bo = _tile(m_out, bm, DEFAULT_BM, BM_ALIGN)
    mop = _round_up(m_out, bo)
    cq = _round_up(c, block)
    grid = (mop // bo,)

    if payload is None:
        xp = _pad_rows(x, mop * k)
        in_specs = [pl.BlockSpec((bo * k, c), lambda i: (i, 0))]
        args = (xp,)
        dec_kw = {}
        kern, kern_enc = _pool_kernel, _pool_enc_kernel
    else:
        in_specs = [pl.BlockSpec((bo * k, c_pad), lambda i: (i, 0)),
                    pl.BlockSpec((bo * k, c_pad // block),
                                 lambda i: (i, 0))]
        args = _pad_payload(payload, mop * k)
        dec_kw = {"c": c}
        kern, kern_enc = _pool_dec_kernel, _pool_dec_enc_kernel
    if not encode:
        extra = dict(block=block, **dec_kw) if dec_kw else {}
        y = pl.pallas_call(
            functools.partial(kern, k=k, **extra),
            grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((bo, c), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((mop, c), jnp.float32),
            interpret=interpret, name=_name("pool", payload, encode))(*args)
        return y[:m_out]
    y, man_o, exp_o = pl.pallas_call(
        functools.partial(kern_enc, block=block, k=k, **dec_kw),
        grid=grid, in_specs=in_specs,
        out_specs=[pl.BlockSpec((bo, c), lambda i: (i, 0)),
                   pl.BlockSpec((bo, cq), lambda i: (i, 0)),
                   pl.BlockSpec((bo, cq // block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mop, c), jnp.float32),
                   jax.ShapeDtypeStruct((mop, cq), jnp.int8),
                   jax.ShapeDtypeStruct((mop, cq // block), jnp.int8)],
        interpret=interpret, name=_name("pool", payload, encode))(*args)
    return y[:m_out], (man_o[:m_out], exp_o[:m_out])


# =============================================================================
# act — relu, the cheapest op that still rides the fused codec
# =============================================================================

def _act_kernel(x_ref, o_ref):
    o_ref[...] = jax.nn.relu(x_ref[...])


def _act_dec_kernel(man_ref, exp_ref, o_ref, *, block, c):
    x = _dequant_vals(man_ref[...], exp_ref[...], block=block)[:, :c]
    o_ref[...] = jax.nn.relu(x)


def _act_enc_kernel(x_ref, o_ref, man_ref, exp_ref, *, block):
    y = jax.nn.relu(x_ref[...])
    o_ref[...] = y
    c = y.shape[1]
    yq = jnp.pad(y, ((0, 0), (0, _round_up(c, block) - c)))
    man_ref[...], exp_ref[...] = _quant_vals(yq, block=block)


def _act_dec_enc_kernel(man_ref, exp_ref, o_ref, yman_ref, yexp_ref, *,
                        block, c):
    x = _dequant_vals(man_ref[...], exp_ref[...], block=block)[:, :c]
    y = jax.nn.relu(x)
    o_ref[...] = y
    yq = jnp.pad(y, ((0, 0), (0, _round_up(c, block) - c)))
    yman_ref[...], yexp_ref[...] = _quant_vals(yq, block=block)


def act_relu(x, *, c: int | None = None, payload=None, encode=False,
             block: int = 32, bm: int = 0, interpret: bool = False):
    """Streaming relu over the row-block grid; fusion flags as in
    :func:`conv2d` (ingress needs ``c``)."""
    if payload is not None:
        assert c is not None, "act ingress fusion needs the channel count"
        man, exp = payload
        m, c_pad = man.shape
        assert c_pad == _round_up(c, block), (man.shape, c, block)
    else:
        m, c = x.shape
    bm = _tile(m, bm, DEFAULT_BM, BM_ALIGN)
    mp = _round_up(m, bm)
    cq = _round_up(c, block)
    grid = (mp // bm,)

    if payload is None:
        in_specs = [pl.BlockSpec((bm, c), lambda i: (i, 0))]
        args = (_pad_rows(x, mp),)
        if not encode:
            y = pl.pallas_call(
                _act_kernel, grid=grid, in_specs=in_specs,
                out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((mp, c), jnp.float32),
                interpret=interpret,
                name=_name("act", payload, encode))(*args)
            return y[:m]
        kern = functools.partial(_act_enc_kernel, block=block)
    else:
        in_specs = [pl.BlockSpec((bm, c_pad), lambda i: (i, 0)),
                    pl.BlockSpec((bm, c_pad // block), lambda i: (i, 0))]
        args = _pad_payload(payload, mp)
        if not encode:
            y = pl.pallas_call(
                functools.partial(_act_dec_kernel, block=block, c=c),
                grid=grid, in_specs=in_specs,
                out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((mp, c), jnp.float32),
                interpret=interpret,
                name=_name("act", payload, encode))(*args)
            return y[:m]
        kern = functools.partial(_act_dec_enc_kernel, block=block, c=c)
    y, man_o, exp_o = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs,
        out_specs=[pl.BlockSpec((bm, c), lambda i: (i, 0)),
                   pl.BlockSpec((bm, cq), lambda i: (i, 0)),
                   pl.BlockSpec((bm, cq // block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, c), jnp.float32),
                   jax.ShapeDtypeStruct((mp, cq), jnp.int8),
                   jax.ShapeDtypeStruct((mp, cq // block), jnp.int8)],
        interpret=interpret, name=_name("act", payload, encode))(*args)
    return y[:m], (man_o[:m], exp_o[:m])


# =============================================================================
# conv_kxk — k x k 'same' conv over a row-major (H*W, C) stripe: line buffer
# =============================================================================

#: bytes of one row block's input in VMEM (lanes padded to 128), the cap
#: on how many image rows a block takes
KXK_BLOCK_BYTES = 2 * 2 ** 20
KXK_VMEM_LIMIT = 64 * 2 ** 20       # scoped VMEM the kernel may claim
KXK_BC = 256                        # out-channel block of a wide conv
#: a conv whose whole contraction ``k*k*cin`` is at most this deep fits one
#: 128-deep MXU pass, so all its k*k taps go into one dot
KXK_FULL_TAP_K = 128


def kxk_full_tap(k: int, cin: int) -> bool:
    """Whether :func:`conv_kxk` takes the full-tap layout (all ``k*k`` taps
    of a ``cin``-deep input side by side, one dot of depth ``k*k*cin``)
    rather than the line-buffer kernel's row-tap one (``k`` dots of depth
    ``k*cin`` a step)."""
    return k * k * cin <= KXK_FULL_TAP_K


@functools.lru_cache(maxsize=None)
def kxk_tiles(h: int, w: int, k: int, cin: int, cout: int
              ) -> tuple[int, int, int]:
    """``(rows, wp, bc)`` of :func:`conv_kxk` on an ``h x w`` image: image
    rows per row block, the row width the kernel walks (``w`` padded to
    whole sublane tiles) and the out-channel block.

    ``rows`` is a multiple of ``2 * (k // 2)`` (the halo block's size must
    divide the row offset it starts at), its input block holds at most
    ``KXK_BLOCK_BYTES`` unless one step of rows exceeds it, and among those
    it reads the fewest image rows per frame, halos included."""
    p = k // 2
    step = max(2 * p, 1)
    wp = _round_up(w, BM_ALIGN)
    row_bytes = wp * _round_up(cin, BC_ALIGN) * 4
    cap = max(step, KXK_BLOCK_BYTES // row_bytes // step * step)
    rows = min(range(step, min(cap, _round_up(h, step)) + 1, step),
               key=lambda r: (-(-h // r) * (r + 2 * p), -r))
    bc = cout if cout <= KXK_BC else KXK_BC
    return rows, wp, bc


def kxk_halo_bytes(h: int, w: int, k: int, cin: int, cout: int) -> int:
    """HBM bytes of halo rows :func:`conv_kxk` reads again per frame: each
    row block's ``k // 2`` image rows above and below, float32."""
    rows, wp, _ = kxk_tiles(h, w, k, cin, cout)
    return -(-h // rows) * 2 * (k // 2) * wp * cin * 4


def _kxk_kernel(*refs, k, rows, wp, w):
    """One (row block, out-channel block) step.  At the first channel
    block the row block and its halo are laid out in the scratch ``k``
    times side by side along the lanes, once per column tap ``dx``:
    shifted along the image row by ``dx - k//2`` (a sublane roll within
    each row) with the columns that fall off the row's edge zeroed.
    Every step then sums ``k`` dots, one per row tap ``dy``: the scratch
    from image row ``dy`` on (an aligned slice of whole rows) against the
    ``(k * cin, bc)`` weights of that row of taps, so each dot contracts
    all ``k`` column taps at once."""
    p = k // 2
    if p:
        xm_ref, xh_ref, w_ref, o_ref, xs_ref = refs
    else:
        xm_ref, w_ref, o_ref, xs_ref = refs
    rr = rows + 2 * p
    cin = xm_ref.shape[-1]

    @pl.when(pl.program_id(1) == 0)
    def _lay_out():
        x = xm_ref[...]
        if p:
            x = jnp.concatenate([x, xh_ref[...]], axis=0)
        x3 = x.reshape(rr, wp, cin)
        col = jax.lax.broadcasted_iota(jnp.int32, x3.shape, 1)
        for dx in range(k):
            s = dx - p
            xs = x3
            if s:
                xs = pltpu.roll(x3, shift=(-s) % wp, axis=1)
                xs = jnp.where((col + s >= 0) & (col + s < w), xs, 0.0)
            xs_ref[:, dx * cin:(dx + 1) * cin] = xs.reshape(rr * wp, cin)

    acc = None
    for dy in range(k):
        part = jnp.dot(xs_ref[dy * wp:(dy + rows) * wp, :], w_ref[dy],
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        acc = part if acc is None else acc + part
    o_ref[...] = acc


def conv_kxk(x, w, *, hw: tuple[int, int], interpret: bool = False):
    """``k x k`` 'same' conv (stride 1, zero padding ``k // 2``) of an image
    held as its row-major ``(H*W, cin)`` stripe; ``w`` is HWIO
    ``(k, k, cin, cout)``.  Returns the ``(H*W, cout)`` stripe, float32.

    The paper's line buffer (Eq. 1 depth ``k * W * cin``) on a row-block
    grid: a step owns ``rows`` whole image rows (:func:`kxk_tiles`) and an
    out-channel block.  Its input arrives as two blocks of the same
    row-padded stripe, the ``rows`` image rows and the ``2 * (k // 2)``
    rows below them, so each block's halo (``k // 2`` rows above and
    below) is read again from HBM and never built: no im2col.  Column
    taps shift within a row in VMEM, mask the row's edges and sit side by
    side, so a row of taps is one dot of depth ``k * cin``.

    A conv whose whole contraction ``k*k*cin`` fits one 128-deep MXU pass
    (:func:`kxk_full_tap`: the RGB stem, 27 deep) takes the full-tap layout
    instead: the ``k*k`` shifted copies of the zero-padded image side by
    side, ``(H*W, k*k*cin)``, and one dot of that depth, both XLA's.  Each
    of the kernel's ``k`` dots would take a whole pass (several at
    ``HIGHEST``) for ``k * cin`` lanes, and XLA keeps the copies
    position-minor, lane-dense, where the kernel's copies of ``cin`` lanes
    pad to 128 (docs/KERNELS.md has the chip's readings of both).

    Tile contract: ``rows`` is a multiple of ``2 * (k // 2)``; the row
    width walked is ``W`` padded to a multiple of 8 (a row of zeros past
    the edge, sliced off after); ``bc`` is ``cout`` up to ``KXK_BC``,
    else ``KXK_BC``, and ``cout`` is padded to it.  Each output value is
    the same ``k`` dots summed in the same order for any tile, so the
    tiling never changes which products it sums.  Its dots run at
    ``HIGHEST`` precision, float32 products on a TPU as off it: through
    the published UNet's 18 such convs a bfloat16 pass would make any
    order of summation other than XLA's conv's grow into a frame's
    bfloat16 rounding noise (PERF.md, section 6)."""
    h, wd = hw
    k, k2, cin, cout = w.shape
    assert k == k2 and k % 2 == 1, w.shape
    assert x.shape == (h * wd, cin), (x.shape, hw, w.shape)
    p = k // 2
    if kxk_full_tap(k, cin):
        x3 = jnp.pad(x.reshape(h, wd, cin), ((p, p), (p, p), (0, 0)))
        taps = [x3[dy:dy + h, dx:dx + wd]
                for dy in range(k) for dx in range(k)]
        return jnp.dot(jnp.concatenate(taps, axis=-1).reshape(h * wd, -1),
                       w.reshape(k * k * cin, cout),
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    rows, wp, bc = kxk_tiles(h, wd, k, cin, cout)
    hp = _round_up(h, rows)
    npad = _round_up(cout, bc)
    x3 = x.reshape(h, wd, cin)
    xp = jnp.pad(x3, ((p, hp - h + p), (0, wp - wd), (0, 0)))
    xp = xp.reshape((hp + 2 * p) * wp, cin)
    wpad = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, npad - cout)))
    wpad = wpad.reshape(k, k * cin, npad)       # a row of taps: dx, then cin
    in_specs = [pl.BlockSpec((rows * wp, cin), lambda i, j: (i, 0))]
    args = [xp]
    if p:
        halo = rows // (2 * p)
        in_specs.append(pl.BlockSpec((2 * p * wp, cin),
                                     lambda i, j: ((i + 1) * halo, 0)))
        args.append(xp)
    in_specs.append(pl.BlockSpec((k, k * cin, bc), lambda i, j: (0, 0, j)))
    y = pl.pallas_call(
        functools.partial(_kxk_kernel, k=k, rows=rows, wp=wp, w=wd),
        grid=(hp // rows, npad // bc), in_specs=in_specs,
        out_specs=pl.BlockSpec((rows * wp, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((hp * wp, npad), jnp.float32),
        scratch_shapes=[pltpu.VMEM(((rows + 2 * p) * wp, k * cin),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=KXK_VMEM_LIMIT),
        interpret=interpret, name="smof_conv_kxk",
    )(*args, wpad)
    y = y[:h * wp, :cout]
    if wp != wd:
        y = y.reshape(h, wp, cout)[:, :wd].reshape(h * wd, cout)
    return y


__all__ = ["conv2d", "dwconv", "pool", "act_relu", "conv_kxk", "kxk_tiles",
           "kxk_full_tap", "DEFAULT_BM", "DEFAULT_BC"]
