"""Pallas kernel tests: interpret-mode allclose against the jnp oracles,
with shape/dtype sweeps and hypothesis property checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.bfp8 import (bfp8_dequant, bfp8_dequant_values,
                                bfp8_quant, bfp8_quant_values)
from repro.kernels.ops import evict_decode, evict_encode, fragmented_matmul
from repro.kernels.streamed_matmul import streamed_matmul, vmem_bytes


class TestStreamedMatmul:
    @pytest.mark.parametrize("M,K,N", [(128, 256, 128), (256, 512, 256),
                                       (128, 384, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref_shapes(self, M, K, N, dtype):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (M, K), dtype)
        ks = 128
        ws = jax.random.normal(key, (ks, N), dtype)
        wd = jax.random.normal(key, (K - ks, N), dtype)
        got = streamed_matmul(x, ws, wd, interpret=True)
        want = ref.streamed_matmul_ref(x, ws, wd)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("static_fraction", [0.0, 0.25, 0.5, 1.0])
    def test_fragmented_matmul_fraction_invisible(self, static_fraction):
        """The m knob changes memory placement, never the math (Eq. 3)."""
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, (128, 512), jnp.float32)
        w = jax.random.normal(key, (512, 256), jnp.float32)
        got = fragmented_matmul(x, w, static_fraction=static_fraction,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)

    def test_block_size_sweep(self):
        key = jax.random.PRNGKey(2)
        x = jax.random.normal(key, (256, 384), jnp.float32)
        ws = jax.random.normal(key, (128, 256), jnp.float32)
        wd = jax.random.normal(key, (256, 256), jnp.float32)
        want = ref.streamed_matmul_ref(x, ws, wd)
        for bm in (128, 256):
            for bn in (128, 256):
                got = streamed_matmul(x, ws, wd, bm=bm, bn=bn, bk=128,
                                      interpret=True)
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           rtol=1e-4, atol=1e-4)

    def test_vmem_accounting_monotonic(self):
        """Bigger static region -> bigger VMEM claim (the Eq. 7 check)."""
        a = vmem_bytes(128, 4096, 128, 128, 128)
        b = vmem_bytes(1024, 4096, 128, 128, 128)
        assert b > a


class TestBFP8Kernel:
    @pytest.mark.parametrize("R,C,block", [(256, 128, 32), (512, 256, 64),
                                           (64, 512, 128)])
    def test_roundtrip_matches_ref(self, R, C, block):
        key = jax.random.PRNGKey(7)
        x = jax.random.normal(key, (R, C), jnp.float32) * 100
        man, exp = bfp8_quant(x, block=block, interpret=True)
        man_r, exp_r = ref.bfp8_quant_ref(x, block=block)
        np.testing.assert_array_equal(np.asarray(man), np.asarray(man_r))
        np.testing.assert_array_equal(np.asarray(exp), np.asarray(exp_r))
        out = bfp8_dequant(man, exp, block=block, interpret=True)
        want = ref.bfp8_dequant_ref(man_r, exp_r, block=block)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6)

    @pytest.mark.parametrize("R,C", [(1, 32), (7, 64), (13, 96), (45, 160),
                                     (3, 1024)])
    @pytest.mark.parametrize("magnitude", [1e-30, 1e-3, 1.0, 1e30])
    def test_value_codec_bitwise_equals_ref(self, R, C, magnitude):
        """The reshape-free codec math is bit for bit the reshaped
        reference on odd shapes: all-zero blocks, exact powers of two and
        magnitudes at both ends of the f32 range included."""
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(R * C), (R, C),
                                         jnp.float32)) * magnitude
        x[0, :32] = 0.0
        if C >= 64:
            x[-1, 32:64] = 2.0 ** np.arange(-10, 22)
        quant = jax.jit(lambda a: bfp8_quant_values(a, block=32))
        man, exp = quant(x)
        man_r, exp_r = jax.jit(lambda a: ref.bfp8_quant_ref(a, block=32))(x)
        np.testing.assert_array_equal(np.asarray(man), np.asarray(man_r))
        np.testing.assert_array_equal(np.asarray(exp), np.asarray(exp_r))
        out = jax.jit(lambda m, e: bfp8_dequant_values(m, e, block=32))(
            man_r, exp_r)
        want = jax.jit(lambda m, e: ref.bfp8_dequant_ref(m, e, block=32))(
            man_r, exp_r)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    @pytest.mark.parametrize("R", [1, 255, 300, 513])
    def test_stripe_kernels_pad_rows(self, R):
        """Row counts that are not a stripe multiple pad and slice back."""
        x = jax.random.normal(jax.random.PRNGKey(R), (R, 64), jnp.float32)
        man, exp = bfp8_quant(x, interpret=True)
        man_r, exp_r = ref.bfp8_quant_ref(x)
        np.testing.assert_array_equal(np.asarray(man), np.asarray(man_r))
        np.testing.assert_array_equal(np.asarray(exp), np.asarray(exp_r))
        np.testing.assert_array_equal(
            np.asarray(bfp8_dequant(man, exp, interpret=True)),
            np.asarray(ref.bfp8_dequant_ref(man_r, exp_r)))

    @given(st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_error_bound_property(self, seed):
        """|x - dequant(quant(x))| <= 2^(exp-7) per block, any input."""
        x = jax.random.normal(jax.random.PRNGKey(seed), (64, 128),
                              jnp.float32) * 10 ** (seed % 5)
        man, exp = evict_encode(x, interpret=True)
        out = evict_decode(man, exp, interpret=True)
        scale = np.exp2(np.asarray(exp, np.float32) - 6.0)
        err = np.abs(np.asarray(x) - np.asarray(out)).reshape(64, 4, 32)
        assert (err <= scale[..., None] * 0.5 + 1e-30).all()

    def test_compression_ratio(self):
        """8-bit mantissa + 1/4 exponent byte per 32-block vs bf16 words."""
        x = jax.random.normal(jax.random.PRNGKey(8), (128, 128), jnp.float32)
        man, exp = evict_encode(x, interpret=True)
        raw_bits = x.size * 16                  # stream words are bf16
        enc_bits = man.size * 8 + exp.size * 8
        assert enc_bits / raw_bits == pytest.approx((8 + 8 / 32) / 16)


# =============================================================================
# Streaming-conv kernel conformance matrix (ISSUE 10)
#
# Locks the contract ``runtime.executor.lower_plan`` relies on: for every
# lowerable op kind, the Pallas body (interpret mode on CPU) is *bit-exact*
# against the reference body on lossless edges, and the fused BFP8 boundary
# codec (ingress dequant / egress quant inside the same ``pallas_call``)
# produces bitwise the payload the unfused ``bfp8_spill_encode`` path
# would — on odd, non-128-aligned shapes.
#
# dwconv caveat: XLA:CPU contracts the tap sum into FMAs when jitted, so
# its reference composition must be *jitted* for bit-exactness (the
# executors always jit; eager comparison would see ~1 ULP drift).
# =============================================================================

from repro.core.builders import _XB, EXEC_MODELS, exec_input_shape
from repro.core.graph import Graph
from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan
from repro.kernels import streaming_conv as SC
from repro.kernels.ops import (KERNEL_REGISTRY, fusable_kinds, kernel_for,
                               lowerable_kinds, resolve_interpret)
from repro.runtime.executor import (FUSABLE_KINDS, _lower_vertex,
                                    analyze_plan, lower_plan)

BLOCK = 32
# odd / non-128-aligned (m, c): m is never a bm multiple, c is never a
# codec-block multiple — every padding path in the kernels is live
ODD_SHAPES = [(28, 24), (45, 40)]
VARIANTS = ("plain", "ingress", "egress", "both")


def _pad_c(a, block=BLOCK):
    c = a.shape[1]
    cp = ((c + block - 1) // block) * block
    return jnp.pad(a, ((0, 0), (0, cp - c)))


def _encode_ref(y):
    """The unfused spill payload: ``bfp8_quant_ref`` of the block-padded
    stripe — what ``bfp8_spill_encode`` produces in reference mode."""
    return ref.bfp8_quant_ref(_pad_c(y), block=BLOCK)


def _decode_ref(payload, c):
    man, exp = payload
    return ref.bfp8_dequant_ref(man, exp, block=BLOCK)[:, :c]


def _kind_io(kind, m, c, key):
    """(x, w, kernel_kwargs, reference_body) for one fusable kind."""
    kx, kw_ = jax.random.split(jax.random.PRNGKey(key))
    x = jax.random.normal(kx, (m, c), jnp.float32)
    extra = {"c": c}
    if kind == "conv":
        cout = c + 16                       # still not a block multiple
        w = jax.random.normal(kw_, (c, cout), jnp.float32) / np.sqrt(c)
        return x, w, extra, lambda xe: ref.conv2d_ref(xe, w)
    if kind == "dwconv":
        w = jax.random.normal(kw_, (3, c), jnp.float32)
        return x, w, extra, lambda xe: ref.dwconv_ref(xe, w)
    if kind == "pool":
        assert m % 2 == 0 or m % 3 == 0
        k = 2 if m % 2 == 0 else 3
        extra["m_out"] = m // k
        return x, None, extra, lambda xe: ref.pool_ref(xe, m // k)
    assert kind == "act"
    return x, None, extra, ref.act_relu_ref


def _call_kernel(kind, x, w, extra, *, payload=None, encode=False, bm=0,
                 bc=0):
    kw = dict(payload=payload, encode=encode, block=BLOCK, bm=bm,
              interpret=True)
    if kind == "conv":
        return SC.conv2d(x, w, bc=bc, **kw)
    if kind == "dwconv":
        return SC.dwconv(x, w, **kw)
    if kind == "pool":
        return SC.pool(x, extra["m_out"], c=extra["c"], **kw)
    return SC.act_relu(x, c=extra["c"], **kw)


class TestKernelConformanceMatrix:
    """Every fusable kind x fusion variant x odd shape: pallas-interpret
    against the (jitted) reference composition, bit-exact."""

    @pytest.mark.parametrize("m,c", ODD_SHAPES)
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("kind", ("conv", "dwconv", "pool", "act"))
    def test_pallas_matches_reference(self, kind, variant, m, c):
        x, w, extra, body = _kind_io(kind, m, c, key=7)
        ingress = variant in ("ingress", "both")
        egress = variant in ("egress", "both")

        payload = _encode_ref(x) if ingress else None
        # reference composition: (decode ->) body (-> encode), jitted as
        # one function exactly like the executors trace it
        def composed(x, payload):
            xe = _decode_ref(payload, c) if ingress else x
            y = body(xe)
            return (y, _encode_ref(y)) if egress else y
        want = jax.jit(composed)(None if ingress else x, payload)

        got = _call_kernel(kind, None if ingress else x, w, extra,
                           payload=payload, encode=egress)
        if egress:
            (gy, (gman, gexp)), (wy, (wman, wexp)) = got, want
            np.testing.assert_array_equal(np.asarray(gy), np.asarray(wy))
            np.testing.assert_array_equal(np.asarray(gman),
                                          np.asarray(wman))
            np.testing.assert_array_equal(np.asarray(gexp),
                                          np.asarray(wexp))
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("kind", ("conv", "dwconv", "pool", "act"))
    def test_fused_codec_respects_bfp8_bound(self, kind):
        """The fused egress payload decodes back within the shared-exponent
        bound (|err| <= half the per-block scale) of the true output."""
        m, c = 28, 24
        x, w, extra, body = _kind_io(kind, m, c, key=11)
        y, payload = _call_kernel(kind, x, w, extra, encode=True)
        back = np.asarray(_decode_ref(payload, np.asarray(y).shape[1]))
        yv = np.asarray(y)
        exp = np.asarray(payload[1], np.float32)
        scale = np.exp2(exp - 6.0)                        # 2^(exp-7) * 2
        err = np.abs(_pad_c(jnp.asarray(yv)) - _pad_c(jnp.asarray(back)))
        err = np.asarray(err).reshape(yv.shape[0], -1, BLOCK)
        assert (err <= scale[..., None] * 0.5 + 1e-30).all()

    @pytest.mark.parametrize("bm,bc", [(5, 7), (28, 24), (128, 128),
                                       (13, 40)])
    def test_tile_sizes_never_change_results(self, bm, bc):
        """bm/bc are pure performance knobs: any tile size, same bits —
        including sizes that do not divide the axes."""
        m, c = 45, 40
        for kind in ("conv", "dwconv", "pool", "act"):
            x, w, extra, body = _kind_io(kind, m, c, key=3)
            base = _call_kernel(kind, x, w, extra, bm=0, bc=0)
            tiled = _call_kernel(kind, x, w, extra, bm=bm, bc=bc)
            np.testing.assert_array_equal(np.asarray(base),
                                          np.asarray(tiled))

    def test_tile_requests_normalise_to_mosaic_tiles(self):
        """A row block is whole 8-row sublane tiles and a channel block
        whole 128-lane tiles, or the full axis; 0 is the default."""
        assert SC._tile(45, 5, SC.DEFAULT_BM, SC.BM_ALIGN) == 8
        assert SC._tile(45, 13, SC.DEFAULT_BM, SC.BM_ALIGN) == 16
        assert SC._tile(45, 0, SC.DEFAULT_BM, SC.BM_ALIGN) == 45
        assert SC._tile(56, 7, SC.DEFAULT_BC, SC.BC_ALIGN) == 56
        assert SC._tile(300, 40, SC.DEFAULT_BC, SC.BC_ALIGN) == 128
        assert SC._tile(300, 0, SC.DEFAULT_BC, SC.BC_ALIGN) == 128

    def test_fused_equals_unfused_same_quant_blocks(self):
        """decode->conv->encode fused into one pallas_call is bitwise the
        three-dispatch pipeline (same quant blocks on both sides)."""
        m, c = 28, 24
        x, w, extra, body = _kind_io("conv", m, c, key=19)
        payload = _encode_ref(x)
        y_f, pay_f = _call_kernel("conv", None, w, extra, payload=payload,
                                  encode=True)
        xe = _decode_ref(payload, c)
        y_u = _call_kernel("conv", xe, w, extra)
        pay_u = _encode_ref(y_u)
        np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))
        np.testing.assert_array_equal(np.asarray(pay_f[0]),
                                      np.asarray(pay_u[0]))
        np.testing.assert_array_equal(np.asarray(pay_f[1]),
                                      np.asarray(pay_u[1]))


class TestKernelRegistry:
    def test_every_lowerable_kind_registered(self):
        assert set(lowerable_kinds()) >= {
            "input", "conv", "matmul", "deconv", "dwconv", "pool", "act",
            "upsample", "add", "mul", "concat", "output"}

    def test_fusable_kinds_match_executor(self):
        assert set(fusable_kinds()) == set(FUSABLE_KINDS)

    def test_dispatch_rows(self):
        body, is_pallas = kernel_for("conv", use_pallas=True)
        assert body is SC.conv2d and is_pallas
        body, is_pallas = kernel_for("conv", use_pallas=False)
        assert body is ref.conv2d_ref and not is_pallas
        # kinds with no Pallas body fall back to reference in pallas mode
        body, is_pallas = kernel_for("concat", use_pallas=True)
        assert body is KERNEL_REGISTRY["concat"].reference and not is_pallas

    def test_resolve_interpret_explicit_wins(self):
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False
        # None falls back to interpret-on-CPU (tests run on CPU)
        assert resolve_interpret(None) is True


# -----------------------------------------------------------------------------
# Graph-level conformance: lower_plan over every lowerable kind
# -----------------------------------------------------------------------------

def _all_kinds_graph():
    """A 12-vertex graph exercising every lowerable op kind once, on odd
    non-aligned shapes (m=28, c=24/40)."""
    g = Graph("allkinds")
    b = _XB(g)
    inp = b.xsimple(None, "input", 24, 28)
    c1 = b.xconv(inp, 24, 40, 28)
    a1 = b.xsimple(c1, "act", 40, 28)
    dw = b.xdwconv(a1, 40, 28)
    po = b.xsimple(dw, "pool", 40, 28, m_out=14)
    up = b.xsimple(po, "upsample", 40, 14, m_out=28)
    ad = b.xsimple([a1, up], "add", 40, 28)
    ml = b.xsimple([ad, dw], "mul", 40, 28)
    mm = b.xconv(ml, 40, 24, 28, kind="matmul")
    dc = b.xconv(mm, 24, 24, 28, kind="deconv")
    cc = b.xsimple([dc, inp], "concat", 48, 28)
    b.xsimple(cc, "output", 48, 28)
    return g


def _chain_graph():
    """Linear chain whose every internal edge has a single-input consumer —
    the topology where *ingress* fusion is legal on every hop."""
    g = Graph("chain")
    b = _XB(g)
    inp = b.xsimple(None, "input", 24, 28)
    c1 = b.xconv(inp, 24, 40, 28)
    a1 = b.xsimple(c1, "act", 40, 28)
    dw = b.xdwconv(a1, 40, 28)
    po = b.xsimple(dw, "pool", 40, 28, m_out=14)
    c2 = b.xconv(po, 40, 24, 14)
    b.xsimple(c2, "output", 24, 14)
    return g


def _evict_all_plan(g, codec):
    g.compute_buffer_depths()
    return ExecutionPlan(
        model=g.name, device="tiny", n_stages=1,
        layers={v.name: LayerPlan(name=v.name) for v in g.vertices()},
        streams=[StreamPlan(e.src, e.dst, evicted=True, codec=codec)
                 for e in g.edges()],
        topo_order=g.topo())


class TestGraphKernelConformance:
    """lower_plan end-to-end: reference vs pallas over {lossless,
    BFP8-evicted} plans covering every lowerable kind."""

    @pytest.mark.parametrize("codec", ["none", "bfp8"])
    def test_all_kinds_bit_exact_across_modes(self, codec):
        g = _all_kinds_graph()
        plan = _evict_all_plan(g, codec)
        x = jax.random.normal(jax.random.PRNGKey(0), (28, 24), jnp.float32)
        yr = np.asarray(lower_plan(g, plan, kernel_mode="reference",
                                   interpret=True)(x))
        yp = np.asarray(lower_plan(g, plan, kernel_mode="pallas",
                                   interpret=True)(x))
        np.testing.assert_array_equal(yr, yp)

    def test_bfp8_stays_near_lossless(self):
        """The compounding BFP8 error across every evicted edge stays small
        — and is non-zero, i.e. the codec really engaged."""
        g = _all_kinds_graph()
        x = jax.random.normal(jax.random.PRNGKey(0), (28, 24), jnp.float32)
        y0 = np.asarray(lower_plan(g, _evict_all_plan(g, "none"),
                                   kernel_mode="pallas", interpret=True)(x))
        yq = np.asarray(lower_plan(g, _evict_all_plan(g, "bfp8"),
                                   kernel_mode="pallas", interpret=True)(x))
        rel = np.linalg.norm(yq - y0) / np.linalg.norm(y0)
        assert 0.0 < rel < 0.2

    def test_chain_exercises_ingress_and_egress_fusion(self):
        """On the all-evicted chain, _lower_vertex fuses both directions
        for every fusable hop — and the fused run stays bit-exact against
        reference mode."""
        g = _chain_graph()
        plan = _evict_all_plan(g, "bfp8")
        an = analyze_plan(g, plan, use_pallas=True, interpret=True)
        fuse_in = [n for n in an.topo if _lower_vertex(g, n, an).fuse_in]
        fuse_out = [n for n in an.topo if _lower_vertex(g, n, an).fuse_out]
        assert len(fuse_in) >= 4 and len(fuse_out) >= 4
        x = jax.random.normal(jax.random.PRNGKey(1), (28, 24), jnp.float32)
        yr = np.asarray(lower_plan(g, plan, kernel_mode="reference",
                                   interpret=True)(x))
        yp = np.asarray(lower_plan(g, plan, kernel_mode="pallas",
                                   interpret=True)(x))
        np.testing.assert_array_equal(yr, yp)

    def test_plan_tile_sizes_thread_through(self):
        """ExecutionPlan.tile_bm/tile_bc reach the kernels and never change
        the bits (the autotune 'tile' move's safety contract)."""
        import dataclasses as dc
        g = _chain_graph()
        plan = _evict_all_plan(g, "bfp8")
        x = jax.random.normal(jax.random.PRNGKey(2), (28, 24), jnp.float32)
        y0 = np.asarray(lower_plan(g, plan, kernel_mode="pallas",
                                   interpret=True)(x))
        yt = np.asarray(lower_plan(g, dc.replace(plan, tile_bm=5,
                                                 tile_bc=7),
                                   kernel_mode="pallas", interpret=True)(x))
        np.testing.assert_array_equal(y0, yt)

    @pytest.mark.parametrize("model", sorted(EXEC_MODELS))
    def test_exec_models_parity(self, model):
        """The acceptance check: every executable model, BFP8-evicted deep
        edges, pallas == reference bit-exactly."""
        g = EXEC_MODELS[model]()
        g.compute_buffer_depths()
        plan = ExecutionPlan(
            model=g.name, device="tiny", n_stages=1,
            layers={v.name: LayerPlan(name=v.name) for v in g.vertices()},
            streams=[StreamPlan(e.src, e.dst,
                                evicted=e.buffer_depth > 2048.0,
                                codec="bfp8" if e.buffer_depth > 2048.0
                                else "none")
                     for e in g.edges()],
            topo_order=g.topo())
        assert any(s.evicted for s in plan.streams), model
        x = jax.random.normal(jax.random.PRNGKey(0), exec_input_shape(g),
                              jnp.float32)
        yr = np.asarray(lower_plan(g, plan, kernel_mode="reference",
                                   interpret=True)(x))
        yp = np.asarray(lower_plan(g, plan, kernel_mode="pallas",
                                   interpret=True)(x))
        np.testing.assert_array_equal(yr, yp)


# =============================================================================
# smof_conv_kxk — the line-buffer k x k conv over (H*W, C) stripes
# =============================================================================

def _conv_same_hwio(x, w, hw):
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x.reshape((1,) + hw + (x.shape[1],)), w, (1, 1), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST).reshape(-1, w.shape[-1])


def _assert_kxk_close(got, x, w, hw):
    """The kernel sums each output's ``k*k*cin`` products tap by tap, XLA's
    conv in its own order: they agree to the float32 reassociation bound
    ``2 * K * eps * sum |x w|``, K = k*k*cin."""
    want = _conv_same_hwio(x, w, hw)
    bound = (2 * w.shape[0] ** 2 * w.shape[2] * np.finfo(np.float32).eps
             * np.asarray(_conv_same_hwio(jnp.abs(x), jnp.abs(w), hw)))
    diff = np.abs(np.asarray(got) - np.asarray(want))
    assert np.all(diff <= bound + 1e-30), (diff.max(), bound.max())


def _kxk_dots(x, w, hw):
    """``(dots, depth)`` of one output row of ``conv_kxk``: how many dots
    it sums and how deep each is, read from what it traces (the weight
    operand of its ``pallas_call``, else its one ``dot_general``)."""
    from repro.kernels import streaming_conv as SC
    eqns = jax.make_jaxpr(
        lambda x, w: SC.conv_kxk(x, w, hw=hw, interpret=True))(x, w).eqns
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    if calls:
        [call] = calls
        return call.invars[-1].aval.shape[:2]
    [dot] = [e for e in eqns if e.primitive.name == "dot_general"]
    return 1, dot.invars[0].aval.shape[-1]


@pytest.fixture
def small_kxk_blocks(monkeypatch):
    """``small_kxk_blocks(w, cin)`` caps ``conv_kxk``'s row blocks at four
    image rows of a ``w``-wide, ``cin``-deep image; the tile cache is
    cleared on both sides so no other test sees these tiles."""
    from repro.kernels import streaming_conv as SC

    def cap(w, cin):
        monkeypatch.setattr(SC, "KXK_BLOCK_BYTES", 4 * SC._round_up(w, 8)
                            * SC._round_up(cin, 128) * 4)
        SC.kxk_tiles.cache_clear()
    yield cap
    SC.kxk_tiles.cache_clear()


class TestConvKxK:
    """``streaming_conv.conv_kxk`` in interpret mode against XLA's conv:
    odd image widths (the kernel pads rows to 8), a height of 261 rows
    that takes several row blocks (for k = 3 two of 132, which 261 is
    no multiple of), the RGB
    stem's 3 channels (the full-tap layout) and widths off the 128 lanes,
    k of 1 and 3."""

    @pytest.mark.parametrize("k", [1, 3])
    @pytest.mark.parametrize("cin", [3, 64, 96])
    @pytest.mark.parametrize("h,w", [(261, 13), (6, 8)])
    def test_matches_lax_conv(self, h, w, cin, k):
        from repro.kernels import streaming_conv as SC
        kx, kw = jax.random.split(jax.random.PRNGKey(h * w + cin + k))
        x = jax.random.normal(kx, (h * w, cin), jnp.float32)
        wt = jax.random.normal(kw, (k, k, cin, 40), jnp.float32)
        rows = SC.kxk_tiles(h, w, k, cin, 40)[0]
        if h == 261:        # k = 3: 2 blocks of 132 rows; k = 1: 3 of 87
            assert rows < h and (h % rows or k == 1), rows
        got = SC.conv_kxk(x, wt, hw=(h, w), interpret=True)
        assert got.shape == (h * w, 40)
        _assert_kxk_close(got, x, wt, (h, w))

    @pytest.mark.parametrize("cin,full", [(3, True), (14, True), (15, False)])
    @pytest.mark.parametrize("h,w", [(261, 13), (6, 8)])
    def test_full_tap_layout(self, h, w, cin, full, small_kxk_blocks):
        """A 3 x 3 conv whose whole contraction fits one 128-deep MXU pass
        (cin 3 and 14: 27 and 126 deep) takes all nine taps in one dot, a
        135-deep one (cin 15) the line-buffer kernel's three dots of a row
        of taps; each matches XLA's conv, and another row block gives the
        same bits."""
        from repro.kernels import streaming_conv as SC
        kx, kw = jax.random.split(jax.random.PRNGKey(h * w + cin))
        x = jax.random.normal(kx, (h * w, cin), jnp.float32)
        wt = jax.random.normal(kw, (3, 3, cin, 40), jnp.float32)
        assert SC.kxk_full_tap(3, cin) == full
        assert _kxk_dots(x, wt, (h, w)) == ((1, 9 * cin) if full
                                            else (3, 3 * cin))
        rows = SC.kxk_tiles(h, w, 3, cin, 40)[0]
        got = SC.conv_kxk(x, wt, hw=(h, w), interpret=True)
        _assert_kxk_close(got, x, wt, (h, w))
        small_kxk_blocks(w, cin)
        assert SC.kxk_tiles(h, w, 3, cin, 40)[0] not in (rows, h)
        np.testing.assert_array_equal(
            got, SC.conv_kxk(x, wt, hw=(h, w), interpret=True))

    def test_wide_cout_tiles_the_channels(self):
        """cout above ``KXK_BC`` runs in channel blocks (300 -> 2 x 256,
        padded), each reusing the row block's shifted copies."""
        from repro.kernels import streaming_conv as SC
        kx, kw = jax.random.split(jax.random.PRNGKey(3))
        x = jax.random.normal(kx, (5 * 8, 16), jnp.float32)
        wt = jax.random.normal(kw, (3, 3, 16, 300), jnp.float32)
        assert SC.kxk_tiles(5, 8, 3, 16, 300)[2] == SC.KXK_BC
        got = SC.conv_kxk(x, wt, hw=(5, 8), interpret=True)
        _assert_kxk_close(got, x, wt, (5, 8))

    @pytest.mark.parametrize("h,w,cin,cout", [
        (368, 480, 64, 64), (184, 240, 128, 128), (92, 120, 256, 256),
        (46, 60, 512, 512), (23, 30, 1024, 1024)])
    def test_tiles_of_the_unet_levels(self, h, w, cin, cout):
        """At each published UNet level the row block is a whole number of
        halo blocks, its input fits the block budget, and it pads the
        image by fewer rows than one block."""
        from repro.kernels import streaming_conv as SC
        rows, wp, bc = SC.kxk_tiles(h, w, 3, cin, cout)
        assert rows % 2 == 0 and wp % 8 == 0 and wp - w < 8
        assert rows * wp * max(cin, 128) * 4 <= SC.KXK_BLOCK_BYTES
        assert -(-h // rows) * rows - h < rows
        assert bc == min(cout, SC.KXK_BC)
        assert SC.kxk_halo_bytes(h, w, 3, cin, cout) == (
            -(-h // rows) * 2 * wp * cin * 4)
