"""Weight-fragmentation matmul (paper §III-B, Fig. 2 -> TPU).

``y = x @ [W_static; W_dyn]`` where the *static* region of the weight matrix
is pinned in VMEM for the whole kernel invocation and the *dynamic* region
streams from HBM block-by-block — exactly the paper's static/dynamic memory
fragmentation with BRAM->VMEM and DDR->HBM.

How the pinning works: ``W_static``'s BlockSpec index_map is constant in the
``m`` (row-block) grid axis, and ``n`` is the OUTERMOST grid dimension, so
Pallas's pipeline revisiting keeps each static column-panel resident in VMEM
across every row block — it is fetched once per ``n`` instead of once per
``(m, n)``.  The dynamic panels are indexed by ``(k, n)`` and double-buffered
by the pipeline, i.e. streamed.  Per-invocation HBM traffic:

    static:   K_s * N                 (fetched once)
    dynamic:  M/bm * K_d * N          (re-fetched for every row block)

so for row-block counts > 1 the static fraction directly cuts HBM bytes —
the Eq. 3/4 trade-off with VMEM capacity as the "on-chip" constraint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xs_ref, xd_ref, ws_ref, wd_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        # static contribution once per (n, m): x_static @ W_static from VMEM
        acc_ref[...] = jnp.dot(xs_ref[...], ws_ref[...],
                               preferred_element_type=jnp.float32)

    acc_ref[...] += jnp.dot(xd_ref[...], wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def streamed_matmul(x: jax.Array, w_static: jax.Array, w_dyn: jax.Array,
                    *, bm: int = 128, bk: int = 128, bn: int = 128,
                    interpret: bool = False) -> jax.Array:
    """x: (M, K); w_static: (Ks, N); w_dyn: (Kd, N); K = Ks + Kd.

    Block sizes default to the MXU-aligned 128; ``Ks`` must be a multiple of
    the VMEM lane tile (128 for f32/bf16) and small enough that a (Ks, bn)
    panel fits VMEM alongside the streaming buffers.
    """
    M, K = x.shape
    Ks, N = w_static.shape
    Kd, N2 = w_dyn.shape
    assert N == N2 and K == Ks + Kd, (x.shape, w_static.shape, w_dyn.shape)
    assert M % bm == 0 and N % bn == 0 and Kd % bk == 0 and Ks % 128 == 0
    nm, nn, nk = M // bm, N // bn, Kd // bk

    x_static = x[:, :Ks]
    x_dyn = x[:, Ks:]

    grid = (nn, nm, nk)   # n outermost => static panel persists across m
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, Ks), lambda n, m, k: (m, 0)),     # x_static
            pl.BlockSpec((bm, bk), lambda n, m, k: (m, k)),     # x_dyn
            pl.BlockSpec((Ks, bn), lambda n, m, k: (0, n)),     # W_static (pinned)
            pl.BlockSpec((bk, bn), lambda n, m, k: (k, n)),     # W_dyn (streamed)
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda n, m, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        # fp32 accumulator tile lives in VMEM across the k loop
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret, name="smof_streamed_matmul",
    )(x_static, x_dyn, w_static, w_dyn)


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def splits_weight(k: int) -> bool:
    """Whether a ``k``-row weight has a dynamic region to stream: more than
    one 128-row panel after padding.  Smaller weights run as a plain dot."""
    return _round_up(k, 128) > 128


def streamed_matmul_padded(x: jax.Array, w: jax.Array, *,
                           static_fraction: float = 0.5, bm: int = 128,
                           bk: int = 128, bn: int = 128,
                           interpret: bool = False) -> jax.Array:
    """``y = x @ w`` through :func:`streamed_matmul` for ARBITRARY shapes.

    The raw kernel needs MXU-aligned dimensions (``M % bm``, ``N % bn``,
    ``Ks % 128``, ``Kd % bk`` all zero); executable layer graphs come with
    whatever channel counts the model dictates.  This wrapper zero-pads
    ``x``/``w`` up to alignment (padded rows/columns contribute exact
    zeros), splits ``w``'s rows at the 128-aligned point closest to
    ``static_fraction`` (the plan's ``1 - m``), and slices the result back.
    A weight matrix too small to split (K <= 128 after padding) falls back
    to a plain dot — there is no dynamic region worth streaming.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    Mp, Np = _round_up(M, bm), _round_up(N, bn)
    if not splits_weight(K):
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(x.dtype)
    Kp = _round_up(K, 128)
    ks = int(round(static_fraction * Kp / 128.0)) * 128
    ks = max(min(ks, Kp - bk), 128)   # >= one static panel + one dyn block
    kd = _round_up(Kp - ks, bk)
    Kp = ks + kd
    xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    y = streamed_matmul(xp, wp[:ks], wp[ks:], bm=bm, bk=bk, bn=bn,
                        interpret=interpret)
    return y[:M, :N]


def vmem_bytes(Ks: int, N: int, bm: int, bk: int, bn: int,
               itemsize: int = 2) -> int:
    """VMEM working set the kernel claims: pinned static panel + double-
    buffered streaming blocks + accumulator (the Eq. 7 on-chip check)."""
    pinned = Ks * bn * itemsize
    stream = 2 * (bm * Ks + bm * bk + bk * bn) * itemsize
    acc = bm * bn * 4 + bm * bn * itemsize
    return pinned + stream + acc
