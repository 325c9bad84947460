"""Executable lowering: DSE plan -> jittable JAX streaming pipeline.

This is the plan->execution bridge: ``core.dse`` decides *where* data lives
(Algorithm 1) and ``core.plan.ExecutionPlan`` records the decision vector;
this module makes those decisions actually happen on an accelerator:

* **evicted streams** (``StreamPlan.evicted``) round-trip through an
  off-chip spill buffer.  BFP8 streams are really quantised on the way out
  and dequantised on the way back in (``kernels/bfp8.py``), so the executed
  numerics carry the codec's error exactly as hardware would; RLE/Huffman
  are lossless, so their numerical effect is identity and only the traffic
  accounting changes.  On TPU the spill additionally hops through the
  host's pinned memory (``jax.device_put`` to ``jax.memory.Space.Host``
  and back) so the bytes truly leave HBM, each array packed lane-dense
  first (:func:`_make_offchip_hop`); elsewhere the hop is a no-op (the
  round-trip through the codec still executes).
* **fragmented weights** (``LayerPlan.weight_static_fraction < 1``)
  dispatch to ``kernels/streamed_matmul.py``: the static row-panel of the
  weight matrix is pinned in VMEM and the dynamic remainder streams from
  HBM block-by-block — the paper's Eq. 3/4 split, with the plan's ``1 - m``
  choosing the split point.
* **stage boundaries** (``LayerPlan.stage`` changes across an edge) hop
  off-chip uncompressed, modelling the sequential subgraph schedule of
  Eq. 5 where inter-partition streams always cross DDR.

Executable graphs come from ``core.builders.build_*_exec``: every vertex
carries ``meta["exec"] = {cin, cout, m[, m_out]}`` and activations flow as
``(positions, channels)`` f32 stripes.  Supported ops of these 1-D graphs:

  ========== =====================================================
  kind       semantics
  ========== =====================================================
  input      identity (the graph input is fed here)
  conv       y = x @ W,  W: (cin, cout)    [1x1 channel mixing]
  matmul     same as conv
  deconv     same as conv (builders pair it with an upsample vertex)
  dwconv     depthwise temporal conv, W: (taps, c) — per-channel mix
             of ``taps`` adjacent positions ('same' padding); the
             3x1x1 temporal kernel of the X3D blocks
  act        relu
  pool       position-axis mean to m_out rows (m -> m_out; m_out=m/2
             is the classic halving pool, m_out=1 the SE global pool)
  upsample   repeat rows m_out/m times      (m -> m_out)
  add        elementwise sum of inputs
  mul        elementwise product of inputs; a (1, c) operand
             broadcasts over positions (SE excitation)
  concat     channel concatenation, predecessor order
  output     ravel-and-concatenate all inputs into one vector
  ========== =====================================================

A spatial graph (``core.builders.build_unet``) adds ``hw``/``hw_out`` (the
input and output image extents), ``k`` and ``stride`` to each spec; its
frame enters as ``(H, W, C)`` and every activation after the input flows
as the image's row-major ``(H*W, C)`` stripe, so crossings, codecs and the
hop see the same ``(m, c)`` stripes as above.  Its weights are HWIO
``(k, k, cin, cout)``:

  ========== =====================================================
  input      flatten the ``(H, W, C)`` frame to its stripe
  conv       k x k 'same' conv, stride 1 (``streaming_conv.conv_kxk``,
             the line-buffer kernel; a conv whose whole contraction
             ``k*k*cin`` fits one 128-deep MXU pass, the RGB stem, takes
             all its taps in one dot: XLA's im2col); k = 1 is a matmul
  deconv     k = stride transposed conv: one matmul to ``k*k*cout``
             channels, then depth-to-space
  pool       k = stride max pool (XLA)
  ========== =====================================================

act, concat and output are the 1-D kinds' bodies.  Every product of a
spatial conv, deconv and matmul is a float32 one (``HIGHEST``): through
the published UNet's 23 convs, a bfloat16 pass makes any difference in
the order of a sum grow into bfloat16 rounding noise (PERF.md, section 6),
so the program could not be checked against a reference.  The matmuls
(k = 1, deconvs) and the stem's one dot are XLA's; a fragmented spatial
weight is refused (ROADMAP B1), and only act fuses the BFP8 codec.

The lowering also emits a :class:`SpillReport`: per evicted/boundary edge,
the raw and off-chip bit volumes.  For BFP8 the off-chip volume is computed
from the actual mantissa/exponent buffer sizes, so when the channel count
is a multiple of the block it is *bit-exact* against the DSE's
compile-time ``c_bar = (8 + 8/block)/word_bits`` (Eq. 2/4).  Each record
also carries :class:`HopTraffic`: the arrays the TPU hop moves for the
edge and the bytes they take on the host link in the chip's tiled layout,
as the hop packs them and as they would cross unpacked.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Callable

import jax
import jax.numpy as jnp

from ..core.graph import Graph
from ..core.plan import ExecutionPlan
from ..kernels import ref as kref
from ..kernels import streaming_conv as SC
from ..kernels.bfp8 import bfp8_dequant, bfp8_quant
from ..kernels.streamed_matmul import (_round_up, splits_weight,
                                      streamed_matmul_padded)
from ..obs.trace import scope

WEIGHT_KINDS = ("conv", "deconv", "matmul")
TEMPORAL_KINDS = ("dwconv",)
LOSSLESS_CODECS = ("none", "rle", "huffman")
BFP8_BLOCK = 32


# =============================================================================
# Spill accounting
# =============================================================================

#: lanes of a TPU vector register: the minor dimension of every array in
#: HBM is tiled to a multiple of this
LANES = 128


def _tiled_bytes(shape: tuple[int, ...], dtype) -> int:
    """Bytes an array of rank >= 2 takes in a TPU's default tiled layout,
    ``T(8,128)`` with ``32/bits`` rows packed per 32-bit sublane word (the
    ``(4,1)`` of an int8 layout): its minor dimension padded to 128 lanes
    and the one above it to ``8 * 32/bits`` rows.  The host link copies
    whole tiles, so this is what one array moves each way."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = 8 * max(1, 4 // itemsize)
    *lead, m, c = shape
    return (math.prod(lead) * _round_up(m, rows) * _round_up(c, LANES)
            * itemsize)


def _lane_dense_rows(shape: tuple[int, ...]) -> int | None:
    """Rows of the ``(n, 128)`` array the hop packs an array of ``shape``
    into, or None where its minor dimension already fills whole lanes."""
    if shape and shape[-1] % LANES == 0:
        return None
    return -(-math.prod(shape) // LANES)


@dataclasses.dataclass(frozen=True)
class HopTraffic:
    """What the TPU's off-chip hop moves over the host link for one spilled
    edge, per frame and in each direction (static: from the shapes the hop
    sees).  Off a TPU the hop is the identity and nothing crosses."""
    arrays: int = 0                # arrays the hop moves
    repacked: int = 0              # of them, packed lane-dense first
    link_bytes: int = 0            # tiled bytes, as the hop moves them
    link_bytes_unpacked: int = 0   # tiled bytes, had each crossed unpacked

    @classmethod
    def of(cls, arrays) -> "HopTraffic":
        """Traffic of the hop over ``arrays`` (anything with ``.shape`` and
        ``.dtype``)."""
        packed = [_lane_dense_rows(tuple(a.shape)) for a in arrays]
        return cls(
            arrays=len(arrays),
            repacked=sum(n is not None for n in packed),
            link_bytes=sum(
                _tiled_bytes(tuple(a.shape) if n is None else (n, LANES),
                            a.dtype) for a, n in zip(arrays, packed)),
            link_bytes_unpacked=sum(_tiled_bytes(tuple(a.shape), a.dtype)
                                    for a in arrays))


@dataclasses.dataclass(frozen=True)
class SpillRecord:
    """Off-chip traffic of one spilled stream (per frame)."""
    src: str
    dst: str
    codec: str
    reason: str            # "evicted" | "stage_boundary"
    raw_bits: int          # words * word_bits before the codec
    offchip_bits: int      # bits actually crossing the off-chip boundary
    exact: bool            # True when offchip_bits is compile-time exact
    hop: HopTraffic = HopTraffic()   # the TPU host link's share of it

    @property
    def ratio(self) -> float:
        return self.offchip_bits / max(self.raw_bits, 1)


@dataclasses.dataclass
class SpillReport:
    spills: list[SpillRecord]
    streamed_weight_bits: int     # dynamic-region weight traffic per frame
    static_weight_bits: int       # pinned on-chip (VMEM) weight residency

    @property
    def total_offchip_bits(self) -> int:
        return (sum(s.offchip_bits for s in self.spills)
                + self.streamed_weight_bits)

    def summary(self) -> dict:
        return {
            "n_spilled_edges": len(self.spills),
            "spill_offchip_bits": sum(s.offchip_bits for s in self.spills),
            "streamed_weight_bits": self.streamed_weight_bits,
            "static_weight_bits": self.static_weight_bits,
            "total_offchip_bits": self.total_offchip_bits,
            "hop_arrays": sum(s.hop.arrays for s in self.spills),
            "hop_repacked": sum(s.hop.repacked for s in self.spills),
            "host_link_bytes": sum(s.hop.link_bytes for s in self.spills),
            "host_link_bytes_unpacked": sum(s.hop.link_bytes_unpacked
                                            for s in self.spills),
            "hop": {f"{s.src}->{s.dst}": dataclasses.asdict(s.hop)
                    for s in self.spills},
        }


def _bfp8_offchip_bits(m: int, c: int, block: int = BFP8_BLOCK) -> int:
    """Mantissa + shared-exponent bits of a (m, c) stripe, after padding the
    channel axis to the codec block (same rounding as _bfp8_roundtrip)."""
    c_pad = _round_up(c, block)
    return m * c_pad * 8 + m * (c_pad // block) * 8


# =============================================================================
# Vertex semantics
# =============================================================================

def _exec_spec(g: Graph, name: str) -> dict:
    v = g.vertex(name)
    spec = v.meta.get("exec")
    if spec is None:
        raise ValueError(
            f"vertex {name!r} has no meta['exec'] — executable lowering "
            f"needs graphs built by core.builders.build_*_exec or "
            f"build_unet")
    return spec


def weight_shape(kind: str, spec: dict) -> tuple[int, ...]:
    """A weighty vertex's weight: ``(taps, c)`` for a dwconv, HWIO
    ``(k, k, cin, cout)`` for a spatial conv or deconv, else
    ``(cin, cout)``."""
    if kind in TEMPORAL_KINDS:
        return (spec.get("taps", 3), spec["cout"])
    if "hw" in spec:
        return (spec["k"], spec["k"], spec["cin"], spec["cout"])
    return (spec["cin"], spec["cout"])


def init_params(g: Graph, seed: int = 0,
                dtype=jnp.float32) -> dict[str, jax.Array]:
    """Deterministic per-vertex weights for every weighty executable op,
    N(0, 1/fan-in) (a dwconv's fan-in is its taps)."""
    params: dict[str, jax.Array] = {}
    for v in g.vertices():
        if v.kind not in WEIGHT_KINDS and v.kind not in TEMPORAL_KINDS:
            continue
        spec = _exec_spec(g, v.name)
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(v.name.encode()))
        shape = weight_shape(v.kind, spec)
        if v.kind in TEMPORAL_KINDS:
            params[v.name] = jax.random.normal(
                key, shape, dtype) / math.sqrt(shape[0])
        else:
            scale = 1.0 / math.sqrt(math.prod(shape[:-1]))
            params[v.name] = scale * jax.random.normal(key, shape, dtype)
    return params


def _pool(x: jax.Array, m_out: int) -> jax.Array:
    m, c = x.shape
    if m % m_out:
        raise ValueError(f"pool needs m_out | m, got {m} -> {m_out}")
    return x.reshape(m_out, m // m_out, c).mean(axis=1)


def _upsample(x: jax.Array, m_out: int) -> jax.Array:
    m = x.shape[0]
    if m_out % m:
        raise ValueError(f"upsample needs m | m_out, got {m} -> {m_out}")
    return jnp.repeat(x, m_out // m, axis=0)


def _dwconv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise temporal conv: per-channel mix of adjacent positions,
    'same' zero padding.  ``w`` is (taps, c)."""
    taps = w.shape[0]
    pad = taps // 2
    xp = jnp.pad(x, ((pad, taps - 1 - pad), (0, 0)))
    m = x.shape[0]
    return sum(w[k][None, :] * xp[k:k + m] for k in range(taps))


def _conv_same(x: jax.Array, w: jax.Array, hw: tuple[int, int]) -> jax.Array:
    """k x k 'same' conv of a ``(H*W, cin)`` stripe by XLA, HWIO ``w``,
    float32 products: the reference-mode body of a spatial conv."""
    k = w.shape[0]
    y = jax.lax.conv_general_dilated(
        x.reshape((1,) + tuple(hw) + (x.shape[1],)), w, (1, 1),
        [(k // 2, k // 2)] * 2, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return y.reshape(-1, w.shape[-1]).astype(x.dtype)


def _tap_matrix(w: jax.Array) -> jax.Array:
    """HWIO ``(k, k, cin, cout)`` as the ``(cin, k*k*cout)`` matrix whose
    columns run over taps, then output channels."""
    return w.transpose(2, 0, 1, 3).reshape(w.shape[2], -1)


def _depth_to_space(y: jax.Array, spec: dict) -> jax.Array:
    """A deconv's ``(h*w, k*k*cout)`` matmul as its ``(h*k * w*k, cout)``
    output stripe: tap ``(a, b)`` of input ``(i, j)`` is output
    ``(i*k + a, j*k + b)``."""
    (h, w), k, c = spec["hw"], spec["k"], spec["cout"]
    return (y.reshape(h, w, k, k, c).transpose(0, 2, 1, 3, 4)
            .reshape(h * k * w * k, c))


def _max_pool(x: jax.Array, spec: dict) -> jax.Array:
    """k = stride max pool of a ``(H*W, c)`` stripe (no padding: trailing
    rows and columns that fill no window drop out)."""
    if spec["k"] != spec["stride"]:
        raise NotImplementedError(
            f"max pool k={spec['k']} stride={spec['stride']}: only k = "
            f"stride pools execute (ROADMAP B2)")
    (h, w), (ho, wo), k = spec["hw"], spec["hw_out"], spec["k"]
    c = x.shape[1]
    x = x.reshape(h, w, c)[:ho * k, :wo * k]
    return x.reshape(ho, k, wo, k, c).max(axis=(1, 3)).reshape(ho * wo, c)


def bfp8_spill_encode(x: jax.Array, *, use_pallas: bool,
                      interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Encode a (m, c) stripe to (mantissas, exponents), padding the channel
    axis to the codec block — the spill buffers that cross off-chip."""
    c = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, _round_up(c, BFP8_BLOCK) - c)))
    if use_pallas:
        return bfp8_quant(xp, block=BFP8_BLOCK, interpret=interpret)
    return kref.bfp8_quant_ref(xp, block=BFP8_BLOCK)


def bfp8_spill_decode(payload: tuple[jax.Array, jax.Array], c: int, *,
                      use_pallas: bool, interpret: bool,
                      dtype=jnp.float32) -> jax.Array:
    """Decode spill buffers back to a (m, c) stripe (drops block padding)."""
    man, exp = payload
    if use_pallas:
        out = bfp8_dequant(man, exp, block=BFP8_BLOCK, dtype=dtype,
                           interpret=interpret)
    else:
        out = kref.bfp8_dequant_ref(man, exp, block=BFP8_BLOCK, dtype=dtype)
    return out[:, :c]


def _bfp8_roundtrip(x: jax.Array, *, use_pallas: bool,
                    interpret: bool) -> jax.Array:
    """Quantise->dequantise a (m, c) stripe through the BFP8 codec.

    Composed from the same encode/decode halves the pipelined streamer
    carries between stages, so the two executors' codec numerics are one
    implementation."""
    payload = bfp8_spill_encode(x, use_pallas=use_pallas, interpret=interpret)
    return bfp8_spill_decode(payload, x.shape[1], use_pallas=use_pallas,
                             interpret=interpret, dtype=x.dtype)


# =============================================================================
# Static plan analysis (shared by the sequential and pipelined executors)
# =============================================================================

@dataclasses.dataclass
class PlanAnalysis:
    """Everything ``lower_plan`` derives from (graph, plan) before tracing.

    Both executors (the sequential one below and the pipelined streamer in
    ``runtime/streamer``) build their traced functions from this one object,
    so spill routing, weight splits, and traffic accounting cannot drift
    between them.
    """
    topo: list[str]                               # deterministic vertex order
    out_shape: dict[str, tuple[int, int]]         # per-vertex (m, c)
    spills: list[SpillRecord]
    spill_fn: dict[tuple[str, str], Callable]     # per spilled edge numerics
    frac: dict[str, float]                        # weight_static_fraction
    stage_of: dict[str, int]                      # vertex -> stage index
    streamed_weight_bits: int
    static_weight_bits: int
    use_pallas: bool
    interpret: bool
    in_vertex: str
    in_shape: tuple[int, ...]         # one frame: (m, c), or (H, W, c)
    #: evicted edges carrying a BFP8 spill — the payload-routed set the
    #: pallas-mode executors encode once per producer / decode per consumer
    bfp8_edges: set = dataclasses.field(default_factory=set)
    #: plan-level Pallas tile sizes (0 = kernel default): row block and,
    #: for the conv family, out-channel block (docs/KERNELS.md)
    tile_bm: int = 0
    tile_bc: int = 0

    @property
    def n_stages(self) -> int:
        return max(self.stage_of.values(), default=0) + 1

    def report(self) -> SpillReport:
        return SpillReport(spills=list(self.spills),
                           streamed_weight_bits=self.streamed_weight_bits,
                           static_weight_bits=self.static_weight_bits)


def analyze_plan(g: Graph, plan: ExecutionPlan | None, *,
                 use_pallas: bool, interpret: bool) -> PlanAnalysis:
    """Static analysis: shapes, spill records/functions, weight traffic."""
    layers = plan.layers if plan is not None else {}
    stream_map = ({(s.src, s.dst): s for s in plan.streams}
                  if plan is not None else {})

    topo = g.topo()
    out_shape: dict[str, tuple[int, int]] = {}
    for name in topo:
        spec = _exec_spec(g, name)
        out_shape[name] = (spec.get("m_out", spec["m"]), spec["cout"])

    stage_of = {n: (layers[n].stage if n in layers else 0) for n in topo}

    spills: list[SpillRecord] = []
    spill_fn: dict[tuple[str, str], Callable] = {}
    bfp8_edges: set = set()
    for e in g.edges():
        u, w = e.src, e.dst
        s = stream_map.get((u, w))
        evicted = bool(s.evicted) if s is not None else False
        codec = s.codec if s is not None else "none"
        cross_stage = stage_of[u] != stage_of[w]
        if not (evicted or cross_stage):
            continue
        m, c = out_shape[u]
        raw_bits = m * c * e.word_bits
        # what run_vertices hops: the BFP8 payload in pallas mode, else the
        # (decoded) float32 stripe.  Both executors report this; in
        # reference mode the pipelined one hops a BFP8 edge that crosses
        # stages as its encoded payload instead.
        hopped = [jax.ShapeDtypeStruct((m, c), jnp.float32)]
        if evicted and codec == "bfp8":
            off_bits, exact = _bfp8_offchip_bits(m, c), True
            fn = functools.partial(_bfp8_roundtrip, use_pallas=use_pallas,
                                   interpret=interpret)
            bfp8_edges.add((u, w))
            if use_pallas:
                c_pad = _round_up(c, BFP8_BLOCK)
                hopped = [
                    jax.ShapeDtypeStruct((m, c_pad), jnp.int8),
                    jax.ShapeDtypeStruct((m, c_pad // BFP8_BLOCK), jnp.int8)]
        elif evicted and codec not in LOSSLESS_CODECS:
            raise ValueError(f"unsupported eviction codec {codec!r} "
                             f"on edge {(u, w)}")
        else:
            # lossless codecs: numerics are identity; traffic is the raw
            # volume (codec "none") — RLE/Huffman would shrink it by a
            # data-dependent ratio the DSE only estimates, so we report
            # the conservative raw volume and flag it non-exact.
            off_bits = raw_bits
            exact = codec == "none"
            fn = lambda x: x                                    # noqa: E731
        spills.append(SpillRecord(
            src=u, dst=w, codec=codec,
            reason="evicted" if evicted else "stage_boundary",
            raw_bits=raw_bits, offchip_bits=off_bits, exact=exact,
            hop=HopTraffic.of(hopped)))
        spill_fn[(u, w)] = fn

    streamed_bits = static_bits = 0
    frac: dict[str, float] = {}
    for name in topo:
        v = g.vertex(name)
        if v.kind not in WEIGHT_KINDS and v.kind not in TEMPORAL_KINDS:
            continue
        lp = layers.get(name)
        f = lp.weight_static_fraction if lp is not None else 1.0
        frac[name] = f
        wbits = (math.prod(weight_shape(v.kind, _exec_spec(g, name)))
                 * v.weight_bits)
        static_bits += int(round(f * wbits))
        streamed_bits += int(round((1.0 - f) * wbits))

    in_vertex = next(n for n in topo if g.vertex(n).kind == "input")
    in_spec = _exec_spec(g, in_vertex)
    in_shape = (tuple(in_spec["hw"]) + (in_spec["cin"],) if "hw" in in_spec
                else out_shape[in_vertex])
    return PlanAnalysis(
        topo=topo, out_shape=out_shape, spills=spills, spill_fn=spill_fn,
        frac=frac, stage_of=stage_of, streamed_weight_bits=streamed_bits,
        static_weight_bits=static_bits, use_pallas=use_pallas,
        interpret=interpret, in_vertex=in_vertex,
        in_shape=in_shape, bfp8_edges=bfp8_edges,
        tile_bm=(plan.tile_bm if plan is not None else 0),
        tile_bc=(plan.tile_bc if plan is not None else 0))


def apply_vertex(v, ins: list[jax.Array], params: dict, x: jax.Array | None,
                 analysis: PlanAnalysis) -> jax.Array:
    """Execute one vertex's semantics — the single source of truth for what
    each op kind *does*, shared by both executors.

    Under the resolved ``kernel_mode="pallas"`` the conv/matmul/deconv,
    dwconv, pool and act bodies dispatch to the ``kernels/streaming_conv``
    Pallas kernels (bit-exact vs the reference bodies, every tile size);
    fragmented weight layers keep the ``streamed_matmul`` fragmentation
    kernel, whose codec stays unfused.  A spatial graph's input, conv,
    deconv and pool run :func:`_apply_spatial` (module doc).  Data-movement and variadic kinds
    (upsample/add/mul/concat/output) run their reference bodies in every
    mode — the registry in ``kernels/ops.py`` records which is which.
    """
    an = analysis
    spec = v.meta.get("exec", {})
    if "hw" in spec and v.kind in ("input", "conv", "deconv", "pool"):
        return _apply_spatial(v, spec, ins, params, x, an)
    if v.kind == "input":
        assert x is not None, "input vertex fed without a graph input"
        return x
    if v.kind in WEIGHT_KINDS:
        h = ins[0]
        f = an.frac.get(v.name, 1.0)
        if f < 1.0 and an.use_pallas:
            return streamed_matmul_padded(h, params[v.name],
                                          static_fraction=f,
                                          interpret=an.interpret)
        if an.use_pallas:
            return SC.conv2d(h, params[v.name], bm=an.tile_bm,
                             bc=an.tile_bc,
                             interpret=an.interpret).astype(h.dtype)
        # reference mode (or fragmented-without-pallas): plain dot
        return jnp.dot(h, params[v.name],
                       preferred_element_type=jnp.float32).astype(h.dtype)
    if v.kind in TEMPORAL_KINDS:
        # the temporal split is not streamable through the matmul kernel;
        # a fragmented dwconv streams per the plan's traffic accounting but
        # executes the full (numerically identical) temporal mix.
        if an.use_pallas:
            return SC.dwconv(ins[0], params[v.name], bm=an.tile_bm,
                             interpret=an.interpret)
        return _dwconv(ins[0], params[v.name])
    if v.kind == "act":
        if an.use_pallas:
            return SC.act_relu(ins[0], bm=an.tile_bm, interpret=an.interpret)
        return jax.nn.relu(ins[0])
    if v.kind == "pool":
        if an.use_pallas:
            return SC.pool(ins[0], an.out_shape[v.name][0], bm=an.tile_bm,
                           interpret=an.interpret)
        return _pool(ins[0], an.out_shape[v.name][0])
    if v.kind == "upsample":
        return _upsample(ins[0], an.out_shape[v.name][0])
    if v.kind == "add":
        return functools.reduce(jnp.add, ins)
    if v.kind == "mul":
        return functools.reduce(jnp.multiply, ins)
    if v.kind == "concat":
        return jnp.concatenate(ins, axis=1)
    if v.kind == "output":
        return jnp.concatenate([i.ravel() for i in ins])
    raise ValueError(f"op kind {v.kind!r} has no executable lowering")


def _apply_spatial(v, spec: dict, ins, params, x, an: PlanAnalysis):
    """The spatial bodies of :func:`apply_vertex` (module doc)."""
    if v.kind == "input":
        assert x is not None, "input vertex fed without a graph input"
        return x.reshape(spec["m"], spec["cin"])
    if v.kind == "pool":
        return _max_pool(ins[0], spec)
    w = params[v.name]
    if an.frac.get(v.name, 1.0) < 1.0:
        raise NotImplementedError(
            f"{v.name}: the plan fragments a spatial {v.kind}'s weight; "
            f"only 1-D graphs stream weights (ROADMAP B1)")
    if v.kind == "conv" and spec["k"] > 1:
        if an.use_pallas:
            return SC.conv_kxk(ins[0], w, hw=tuple(spec["hw"]),
                               interpret=an.interpret)
        return _conv_same(ins[0], w, tuple(spec["hw"]))
    y = jnp.dot(ins[0], _tap_matrix(w), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    return _depth_to_space(y, spec) if v.kind == "deconv" else y


# =============================================================================
# Kernel-level vertex lowering: Pallas bodies + fused BFP8 boundary codec
# =============================================================================

#: kinds whose Pallas body can fuse the BFP8 boundary codec (mirrors
#: kernels.ops.fusable_kinds(); kept literal here so the executor does not
#: import the jitted wrapper layer)
FUSABLE_KINDS = ("conv", "deconv", "matmul", "dwconv", "pool", "act")


def vertex_body(g: Graph, name: str, an: PlanAnalysis) -> str:
    """Which body :func:`apply_vertex` runs for one vertex: ``"pallas"`` or
    ``"reference"``.  Pallas mode still runs reference bodies for the
    data-movement kinds and for a fragmented weight too small to split
    (``streamed_matmul_padded``'s plain-dot fallback), and for a spatial
    graph's max pools, matmuls and the stem's im2col dot — this names
    them, so a silent fallback shows up in a script's output."""
    v = g.vertex(name)
    spec = _exec_spec(g, name)
    if not an.use_pallas or v.kind not in FUSABLE_KINDS:
        return "reference"
    if "hw" in spec and v.kind != "act":
        return ("pallas" if v.kind == "conv" and spec["k"] > 1
                and not SC.kxk_full_tap(spec["k"], spec["cin"])
                else "reference")
    if v.kind in WEIGHT_KINDS and an.frac.get(name, 1.0) < 1.0:
        return ("pallas" if splits_weight(_exec_spec(g, name)["cin"])
                else "reference")
    return "pallas"


@dataclasses.dataclass(frozen=True)
class VertexLowering:
    """``_lower_vertex``'s decision record for one vertex under the
    resolved kernel mode."""
    fuse_in: tuple[str, str] | None  # bfp8 in-edge decoded inside the kernel
    fuse_out: bool                   # kernel also emits the spill payload
    needs_payload: bool              # some out-edge carries a bfp8 spill


def _lower_vertex(g: Graph, name: str, an: PlanAnalysis) -> VertexLowering:
    """Decide one vertex's kernel-level lowering: in pallas mode a fusable
    kind with an un-fragmented weight fuses a *single* BFP8-evicted input
    edge (ingress dequant inside the ``pallas_call``) and/or emits its
    output's spill payload from the same call (egress quant).  Multi-input
    consumers and fragmented weight layers fall back to the standalone
    ``bfp8_spill_decode``/``bfp8_spill_encode`` dispatches, and so does
    every spatial kind but act."""
    v = g.vertex(name)
    spec = _exec_spec(g, name)
    needs_payload = an.use_pallas and any(
        (name, s) in an.bfp8_edges for s in g.successors(name))
    fusable = (an.use_pallas and v.kind in FUSABLE_KINDS
               and not (v.kind in WEIGHT_KINDS
                        and an.frac.get(name, 1.0) < 1.0)
               and ("hw" not in spec or v.kind == "act"))
    fuse_in = None
    if fusable:
        in_edges = g.in_edges(name)
        if len(in_edges) == 1 and (in_edges[0].src, name) in an.bfp8_edges:
            fuse_in = (in_edges[0].src, name)
    return VertexLowering(fuse_in=fuse_in,
                          fuse_out=fusable and needs_payload,
                          needs_payload=needs_payload)


def apply_vertex_fused(v, ins, params, x, analysis: PlanAnalysis, *,
                       payload_in=None, want_payload: bool = False):
    """``apply_vertex`` with the fused BFP8 boundary codec.

    ``payload_in`` is the (mantissa, exponent) spill payload of the
    vertex's single input edge — dequantised per block *inside* the Pallas
    kernel; ``want_payload=True`` asks the same ``pallas_call`` to also
    quantise and emit the output's spill payload.  Returns
    ``(y, payload | None)``.  Callers consult :func:`_lower_vertex` for
    legality; with neither flag this is exactly ``apply_vertex``.
    """
    an = analysis
    if payload_in is None and not want_payload:
        return apply_vertex(v, ins, params, x, an), None
    assert an.use_pallas and v.kind in FUSABLE_KINDS, (v.kind, an.use_pallas)
    xin = ins[0] if payload_in is None else None
    kw = dict(payload=payload_in, encode=want_payload, block=BFP8_BLOCK,
              bm=an.tile_bm, interpret=an.interpret)
    if v.kind in WEIGHT_KINDS:
        out = SC.conv2d(xin, params[v.name], bc=an.tile_bc, **kw)
    elif v.kind in TEMPORAL_KINDS:
        out = SC.dwconv(xin, params[v.name], **kw)
    elif v.kind == "pool":
        out = SC.pool(xin, an.out_shape[v.name][0],
                      c=an.out_shape[v.name][1], **kw)
    else:                       # act
        out = SC.act_relu(xin, c=an.out_shape[v.name][1], **kw)
    return out if want_payload else (out, None)


def line_buffers(g: Graph, *, use_pallas: bool) -> dict[str, dict]:
    """Per k x k conv vertex of a spatial graph: the body that runs it
    and, in pallas mode, its layout (``streaming_conv.conv_kxk``): the
    taps each dot takes and its depth, ``k*k`` taps for XLA's im2col, ``k``
    for the line-buffer kernel, and for the kernel its image rows per row
    block, the halo rows each block reads again (``k // 2`` above and
    below) and the halo bytes re-read per frame; beside them the DSE's
    Eq. 1 depth of the vertex's line buffer (``k * W * cin`` words,
    ``Vertex.base_depth``)."""
    out = {}
    for v in g.vertices():
        spec = v.meta.get("exec", {})
        if v.kind != "conv" or "hw" not in spec or spec["k"] == 1:
            continue
        (h, w), k, cin = spec["hw"], spec["k"], spec["cin"]
        rec = {"kernel": "xla", "eq1_depth_words": v.base_depth}
        if use_pallas and SC.kxk_full_tap(k, cin):
            rec.update(kernel="xla_im2col", taps_per_dot=k * k,
                       dot_depth=k * k * cin)
        elif use_pallas:
            rows, _, _ = SC.kxk_tiles(h, w, k, cin, spec["cout"])
            rec.update(kernel="smof_conv_kxk", taps_per_dot=k,
                       dot_depth=k * cin, rows_per_block=rows,
                       halo_rows=2 * (k // 2),
                       halo_bytes_per_frame=SC.kxk_halo_bytes(
                           h, w, k, cin, spec["cout"]))
        out[v.name] = rec
    return out


def run_vertices(g: Graph, an: PlanAnalysis, names: list[str], params: dict,
                 x: jax.Array | None, external, hop):
    """The one per-vertex execution loop both executors trace.

    Runs ``names`` (a topo-ordered subset of the graph) with
    payload-routed BFP8 eviction: in pallas mode the producer of a
    BFP8-evicted edge encodes the spill once (fused into its kernel when
    :func:`_lower_vertex` allows) and every consumer decodes it (fused
    likewise, else via ``bfp8_spill_decode``); in reference mode every
    spilled edge round-trips through ``spill_fn`` — numerically the same
    composition either way, which is what the kernel conformance matrix
    locks.  ``external(edge)`` resolves in-edges whose producer is outside
    ``names`` (the pipelined streamer's decoded crossing reads); pass
    ``None`` for a whole-graph run.  Returns ``(values, payloads)``.

    The device ops it emits are named (``obs.trace.scope``):
    ``smof.<kind>:<vertex>`` for a vertex's lowering, kernel, pads and
    slices alike; ``smof.codec.enc:<vertex>`` and
    ``smof.codec.dec:<src>-<dst>`` for the standalone BFP8 kernels, and
    ``smof.codec:<src>-<dst>`` for a reference-mode spill round-trip.
    The hop's copies to host memory and back carry no ``op_name`` once
    compiled (XLA makes them from memory spaces); a trace shows them by
    memory space ``S(5)``.  The reshapes that pack a payload lane-dense
    for the hop and unpack it carry the enclosing scope (``smof.tick`` in
    the pipelined step).
    """
    internal = set(names)
    values: dict[str, jax.Array] = {}
    payloads: dict[str, tuple] = {}
    for name in names:
        v = g.vertex(name)
        lv = _lower_vertex(g, name, an)
        ins, payload_in = [], None
        for e in g.in_edges(name):      # predecessor order = operand order
            edge = (e.src, name)
            if e.src not in internal:
                ins.append(external(edge))
                continue
            link = f"{e.src}-{name}"
            if an.use_pallas and edge in an.bfp8_edges:
                pay = jax.tree.map(hop, payloads[e.src])
                if lv.fuse_in == edge:
                    payload_in = pay
                    ins.append(None)
                else:
                    with scope("codec.dec", link):
                        ins.append(bfp8_spill_decode(
                            pay, an.out_shape[e.src][1], use_pallas=True,
                            interpret=an.interpret))
            else:
                val = values[e.src]
                fn = an.spill_fn.get(edge)
                if fn is not None:
                    with scope("codec", link):
                        val = fn(val)
                    val = hop(val)
                ins.append(val)
        with scope(v.kind, name):
            y, pay = apply_vertex_fused(v, ins, params, x, an,
                                        payload_in=payload_in,
                                        want_payload=lv.fuse_out)
        values[name] = y
        if lv.needs_payload:
            if pay is None:
                with scope("codec.enc", name):
                    pay = bfp8_spill_encode(y, use_pallas=True,
                                            interpret=an.interpret)
            payloads[name] = pay
    return values, payloads


# =============================================================================
# Lowering
# =============================================================================

@dataclasses.dataclass
class LoweredPipeline:
    """A jitted executable form of one ExecutionPlan.

    ``fn(params, x)`` runs the whole streaming pipeline; ``report`` is the
    static off-chip traffic accounting the lowering derived from the plan.
    """
    fn: Callable[[dict, jax.Array], jax.Array]
    params: dict[str, jax.Array]
    report: SpillReport
    plan: ExecutionPlan | None
    graph_name: str
    # oracle entry (repro.testing): un-jitted forward that returns every
    # vertex's output, for localising where two executors diverge
    values_fn: Callable[[dict, jax.Array], dict] | None = None

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.fn(self.params, x)

    def run_intermediates(self, x: jax.Array) -> dict[str, jax.Array]:
        """Every vertex's output for one frame, in topo order.

        The conformance oracles (``repro.testing.oracle``) use this to name
        the *first* vertex where a plan's numerics leave the reference —
        far more actionable than "final outputs differ".  Un-jitted: this
        is a debugging path, not an execution path.
        """
        if self.values_fn is None:
            raise NotImplementedError("this pipeline was lowered without "
                                      "intermediate capture")
        return self.values_fn(self.params, x)

    def run_traced(self, x: jax.Array, recorder=None) -> jax.Array:
        """Run one frame, recording a ``frame`` span plus spill counters.

        The sequential executor has no tick structure, so the telemetry is
        one host-side wall-clock span per frame and one
        ``emit_spill_counters`` round-trip per :class:`SpillRecord` (every
        evicted edge crosses off-chip exactly once per frame here).  With
        ``recorder=None`` this is exactly ``self(x)``.
        """
        from ..obs.stream import emit_spill_counters
        from ..obs.trace import NULL_RECORDER

        rec = NULL_RECORDER if recorder is None else recorder
        with rec.span("frame", track="host",
                      args={"graph": self.graph_name}):
            y = self.fn(self.params, x)
            jax.block_until_ready(y)
        ts = rec.now()
        for r in self.report.spills:
            emit_spill_counters(rec, r, ts=ts)
        return y


def resolve_kernel_mode(kernel_mode: str,
                        interpret: bool | None) -> tuple[bool, bool]:
    """Kernel-dispatch policy shared by both executors: returns
    (use_pallas, interpret) for a requested mode on the current backend.

    On a TPU the Pallas kernels run compiled: an explicit
    ``interpret=True`` (e.g. replayed from an artifact saved on a CPU
    host) is refused rather than silently timing the interpreter."""
    if kernel_mode not in ("auto", "pallas", "reference"):
        raise ValueError(f"unknown kernel_mode {kernel_mode!r}")
    on_tpu = jax.default_backend() == "tpu"
    use_pallas = kernel_mode == "pallas" or (kernel_mode == "auto" and on_tpu)
    if interpret is None:
        interpret = not on_tpu
    elif interpret and use_pallas and on_tpu:
        raise ValueError(
            "interpret=True on a TPU would run the Pallas kernels in the "
            "interpreter instead of on the chip; compile with "
            "interpret=None (or False)")
    return use_pallas, interpret


def _make_offchip_hop() -> Callable[[jax.Array], jax.Array]:
    """Real off-chip placement for an evicted spill: on TPU the value is
    moved to the host's pinned memory and back inside the jitted step
    (``jax.device_put`` to ``jax.memory.Space.Host`` / ``.Device``), so the
    bytes truly leave HBM.  Other platforms have no separate device memory
    to leave, and the hop is the identity.  A TPU without a ``pinned_host``
    memory kind is an error, not a silent identity.  Called once at
    lowering time, not per trace.

    The copies move whole tiles of HBM's layout, 128 lanes wide, so an
    array whose minor dimension is not a multiple of 128 (a BFP8 payload's
    exponents, 64-channel mantissas) would carry its lane padding across
    the link both ways.  The hop packs such an array lane-dense first:
    flattened, zero-padded to whole rows of 128 and reshaped to
    ``(n, 128)``, then sliced and reshaped back on return.  Bytes are
    permuted, never changed.  :class:`HopTraffic` counts what crosses.

    The packed array is held behind an optimization barrier: without it
    XLA folds the unpacking reshape into a row pad that follows (every
    payload that a dequant kernel reads is padded to its row blocks) and
    drops the host copies along with the packing, so the payload never
    leaves HBM."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        return lambda x: x
    kinds = {m.kind for m in device.addressable_memories()}
    if "pinned_host" not in kinds:
        raise RuntimeError(
            f"{device.device_kind} exposes no pinned_host memory (kinds: "
            f"{sorted(kinds)}): evicted spills cannot leave HBM")

    def move(x: jax.Array) -> jax.Array:
        y = jax.device_put(x, jax.memory.Space.Host)
        return jax.device_put(y, jax.memory.Space.Device)

    def hop(x: jax.Array) -> jax.Array:
        n = _lane_dense_rows(x.shape)
        if n is None:
            return move(x)
        flat = jnp.pad(x.reshape(-1), (0, n * LANES - x.size))
        y = jax.lax.optimization_barrier(move(flat.reshape(n, LANES)))
        return y.reshape(-1)[:x.size].reshape(x.shape)
    return hop


def lower_plan(g: Graph, plan: ExecutionPlan | None = None, *,
               kernel_mode: str = "auto", seed: int = 0,
               interpret: bool | None = None) -> LoweredPipeline:
    """Lower ``plan`` over executable graph ``g`` to a jitted pipeline.

    plan=None lowers the dense reference: no eviction, no fragmentation,
    one stage — the numerical baseline every plan must match (lossless
    codecs) or approximate (BFP8).

    This is the low-level entry; the documented path is the compile façade
    (``repro.compile(CompileSpec(mode="staged"))``), which produces
    bit-identical executors and adds search, serving, and persistence.

    kernel_mode: "pallas" dispatches conv/dwconv/pool/act to the
    ``kernels/streaming_conv`` row-block kernels (with the BFP8 boundary
    codec fused at evicted edges), fragmented matmuls to
    ``streamed_matmul``, and the standalone codec to the bfp8 stripe
    kernels (interpret-mode off TPU); "reference" uses the pure-jnp
    oracles, "auto" picks pallas on TPU and reference elsewhere.  The two
    modes are bit-exact against each other (tests/test_kernels.py).
    """
    use_pallas, interpret = resolve_kernel_mode(kernel_mode, interpret)
    hop = _make_offchip_hop()
    an = analyze_plan(g, plan, use_pallas=use_pallas, interpret=interpret)

    # -- build the traced pipeline -------------------------------------------
    def forward_values(params: dict, x: jax.Array) -> dict[str, jax.Array]:
        if tuple(x.shape) != an.in_shape:
            # every op downstream is shape-agnostic on the position axis, so
            # a wrong-m input would execute silently while the SpillReport
            # described the declared shapes — refuse at trace time instead
            raise ValueError(
                f"input shape {tuple(x.shape)} does not match the graph's "
                f"input spec {an.in_shape} for {g.name!r}")
        values, _ = run_vertices(g, an, an.topo, params, x, None, hop)
        return values

    def forward(params: dict, x: jax.Array) -> jax.Array:
        return forward_values(params, x)[an.topo[-1]]

    return LoweredPipeline(fn=jax.jit(forward),
                           params=init_params(g, seed=seed),
                           report=an.report(), plan=plan, graph_name=g.name,
                           values_fn=forward_values)


def reference_pipeline(g: Graph, *, seed: int = 0) -> LoweredPipeline:
    """The dense, un-evicted, un-fragmented baseline pipeline."""
    return lower_plan(g, None, kernel_mode="reference", seed=seed)
