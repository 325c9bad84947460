"""Executable lowering tests: DSE plan -> JAX pipeline.

The contract under test (docs/ARCHITECTURE.md):
* lossless plans execute numerically identical to the dense reference,
  no matter how aggressively the DSE evicted/fragmented/partitioned;
* BFP8-evicted streams really round-trip through the codec, and their
  off-chip traffic accounting is bit-exact against the compile-time
  c_bar = (8 + 8/block) / word_bits;
* fragmented weights dispatch to the Pallas streamed_matmul with the
  plan's static/dynamic split and stay numerically invisible.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DSEConfig, build_unet_exec, build_yolo_head_exec,
                        plan_from_dse, run_dse)
from repro.core.compression import bfp8_decode, bfp8_encode, bfp8_ratio
from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan
from repro.core.resources import Device
from repro.runtime.executor import (LoweredPipeline, SpillReport,
                                    _bfp8_roundtrip, _make_offchip_hop,
                                    analyze_plan, init_params, lower_plan,
                                    reference_pipeline, resolve_kernel_mode,
                                    vertex_body)

TINY = Device("tiny", compute_units=4096, onchip_bits=300_000,
              offchip_gbps=64.0, freq_mhz=500.0, reconfig_s=0.0)


def _dse_plan(g, codecs=("none",), cut_kinds=("output",), dev=TINY):
    res = run_dse(g, dev, DSEConfig(batch=1, codecs=codecs, word_bits=16,
                                    cut_kinds=cut_kinds))
    return plan_from_dse(g.name, dev.name, res), res


class TestParity:
    def test_lossless_plan_matches_reference_unet(self):
        """Acceptance: DSE-chosen evicted/fragmented plan == dense baseline."""
        g = build_unet_exec()
        plan, _ = _dse_plan(g)
        assert any(s.evicted for s in plan.streams), "device should force eviction"
        assert any(lp.weight_static_fraction < 1.0
                   for lp in plan.layers.values()), "should force fragmentation"
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
        ref = reference_pipeline(g)
        low = lower_plan(g, plan, kernel_mode="reference")
        np.testing.assert_allclose(np.asarray(low(x)), np.asarray(ref(x)),
                                   rtol=1e-5, atol=1e-5)

    def test_lossless_plan_matches_reference_yolo_head(self):
        g = build_yolo_head_exec()
        plan, _ = _dse_plan(g)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
        ref = reference_pipeline(g)
        low = lower_plan(g, plan, kernel_mode="reference")
        np.testing.assert_allclose(np.asarray(low(x)), np.asarray(ref(x)),
                                   rtol=1e-5, atol=1e-5)

    def test_multi_stage_plan_matches_reference(self):
        """Stage-boundary off-chip hops stay numerically invisible."""
        g = build_unet_exec()
        plan, _ = _dse_plan(g, cut_kinds=("pool", "conv"))
        assert plan.n_stages > 1
        x = jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32)
        ref = reference_pipeline(g)
        low = lower_plan(g, plan, kernel_mode="reference")
        np.testing.assert_allclose(np.asarray(low(x)), np.asarray(ref(x)),
                                   rtol=1e-5, atol=1e-5)
        assert any(s.reason == "stage_boundary" for s in low.report.spills)

    def test_pallas_dispatch_matches_reference(self):
        """Fragmented layers through the real streamed_matmul kernel.

        The graph must contain layers with cin > 128, or the padded wrapper
        legitimately falls back to a plain dot (nothing to stream) and the
        kernel never runs — yolo_head_exec's neck convs reach cin=192.
        Every weighty layer is force-fragmented at m=0.5 so dispatch does
        not depend on what the DSE happens to choose.
        """
        from unittest import mock

        from repro.kernels import streamed_matmul as sm
        from repro.runtime.executor import WEIGHT_KINDS

        g = build_yolo_head_exec()
        layers = {}
        for v in g.vertices():
            f = 0.5 if v.kind in WEIGHT_KINDS else 1.0
            layers[v.name] = LayerPlan(name=v.name, weight_static_fraction=f)
        streams = [StreamPlan(e.src, e.dst) for e in g.edges()]
        plan = ExecutionPlan(model=g.name, device="tiny", n_stages=1,
                             layers=layers, streams=streams)
        x = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
        ref = reference_pipeline(g)
        real_kernel = sm.streamed_matmul
        with mock.patch.object(sm, "streamed_matmul",
                               side_effect=real_kernel) as spy:
            low = lower_plan(g, plan, kernel_mode="pallas")
            y = low(x)
        assert spy.call_count > 0, "no layer dispatched to the Pallas kernel"
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x)),
                                   rtol=2e-4, atol=2e-4)


class TestBFP8Eviction:
    def _plan_with_bfp8_skip(self, g):
        """Hand-written plan: evict every >1-consumer skip edge with BFP8."""
        layers = {v.name: LayerPlan(name=v.name) for v in g.vertices()}
        streams = []
        for e in g.edges():
            evict = e.buffer_depth > 4096.0
            streams.append(StreamPlan(e.src, e.dst, evicted=evict,
                                      codec="bfp8" if evict else "none"))
        assert any(s.evicted for s in streams)
        return ExecutionPlan(model=g.name, device="tiny", n_stages=1,
                             layers=layers, streams=streams)

    def test_roundtrip_ratio_matches_compile_time_constant(self):
        """Satellite acceptance: spill bits / raw bits == 8.25/16 exactly."""
        g = build_unet_exec()
        g.compute_buffer_depths()
        plan = self._plan_with_bfp8_skip(g)
        low = lower_plan(g, plan, kernel_mode="reference")
        evicted = [s for s in low.report.spills if s.reason == "evicted"]
        assert evicted
        for s in evicted:
            assert s.exact
            assert s.ratio == bfp8_ratio(16, block=32) == (8 + 8 / 32) / 16

    def test_bfp8_error_small_and_nonzero(self):
        """The codec really runs: output differs, but only by ~8-bit error."""
        g = build_unet_exec()
        g.compute_buffer_depths()
        plan = self._plan_with_bfp8_skip(g)
        x = jax.random.normal(jax.random.PRNGKey(4), (64, 32), jnp.float32)
        ref = reference_pipeline(g)
        low = lower_plan(g, plan, kernel_mode="reference")
        yr, yl = np.asarray(ref(x)), np.asarray(low(x))
        rel = np.abs(yl - yr).max() / np.abs(yr).max()
        assert 0.0 < rel < 0.15, rel

    def test_jax_roundtrip_matches_numpy_codec(self):
        """The in-pipeline codec and core.compression agree on real data."""
        rng = np.random.default_rng(5)
        x = rng.normal(size=(16, 64)).astype(np.float32)
        want = bfp8_decode(bfp8_encode(x, block=32))
        got = np.asarray(_bfp8_roundtrip(jnp.asarray(x), use_pallas=False,
                                         interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

    def test_pallas_and_reference_codec_agree(self):
        x = jax.random.normal(jax.random.PRNGKey(6), (8, 96), jnp.float32)
        a = _bfp8_roundtrip(x, use_pallas=True, interpret=True)
        b = _bfp8_roundtrip(x, use_pallas=False, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


class TestReport:
    def test_spill_report_totals(self):
        g = build_unet_exec()
        plan, res = _dse_plan(g, codecs=("none", "bfp8"))
        low = lower_plan(g, plan, kernel_mode="reference")
        r = low.report
        assert isinstance(r, SpillReport)
        s = r.summary()
        assert s["total_offchip_bits"] == (s["spill_offchip_bits"]
                                          + s["streamed_weight_bits"])
        # every evicted stream in the plan is accounted for
        n_evicted = sum(1 for st in plan.streams if st.evicted)
        assert sum(1 for sp in r.spills if sp.reason == "evicted") == n_evicted

    def test_static_plus_streamed_is_total_weight_bits(self):
        g = build_unet_exec()
        plan, _ = _dse_plan(g)
        low = lower_plan(g, plan, kernel_mode="reference")
        total = sum(int(v.weight_words) * v.weight_bits for v in g.vertices())
        r = low.report
        assert r.static_weight_bits + r.streamed_weight_bits == total


class TestLoweringErrors:
    def test_non_exec_graph_rejected(self):
        from repro.core import build_yolov8n
        with pytest.raises(ValueError, match="exec"):
            reference_pipeline(build_yolov8n())

    def test_unknown_codec_rejected(self):
        g = build_unet_exec()
        layers = {v.name: LayerPlan(name=v.name) for v in g.vertices()}
        streams = [StreamPlan(e.src, e.dst, evicted=True, codec="lzw")
                   for e in g.edges()]
        plan = ExecutionPlan(model=g.name, device="tiny", n_stages=1,
                             layers=layers, streams=streams)
        with pytest.raises(ValueError, match="codec"):
            lower_plan(g, plan)

    def test_params_deterministic(self):
        g = build_unet_exec(positions=32, levels=2)
        p1, p2 = init_params(g, seed=3), init_params(g, seed=3)
        assert set(p1) == set(p2)
        for k in p1:
            np.testing.assert_array_equal(np.asarray(p1[k]),
                                          np.asarray(p2[k]))

    def test_lowered_pipeline_callable(self):
        g = build_unet_exec(positions=32, levels=2)
        ref = reference_pipeline(g)
        assert isinstance(ref, LoweredPipeline)
        x = jnp.zeros((32, 32), jnp.float32)
        assert ref(x).shape == (32 * 32,)


class _Memory:
    def __init__(self, kind):
        self.kind = kind


class _FakeTpu:
    """A TPU device as ``jax.devices()`` would list it, with chosen memory
    kinds — steers the hop's TPU branch on a CPU host."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, kinds):
        self._kinds = kinds

    def addressable_memories(self):
        return [_Memory(k) for k in self._kinds]


class TestDevicePath:
    """What decides where a run's work lands: the off-chip hop, the kernel
    mode on a TPU, the placement, and which body runs each vertex."""

    def test_offchip_hop_is_identity_off_tpu(self):
        assert jax.devices()[0].platform == "cpu"
        x = jnp.arange(6.0)
        assert _make_offchip_hop()(x) is x

    def test_offchip_hop_raises_on_tpu_without_host_memory(self,
                                                           monkeypatch):
        monkeypatch.setattr(jax, "devices",
                            lambda *a, **k: [_FakeTpu(["device"])])
        with pytest.raises(RuntimeError, match="pinned_host"):
            _make_offchip_hop()

    def test_offchip_hop_on_tpu_round_trips_values(self, monkeypatch):
        monkeypatch.setattr(
            jax, "devices",
            lambda *a, **k: [_FakeTpu(["device", "pinned_host"])])
        hop = _make_offchip_hop()
        x = jnp.arange(12.0).reshape(3, 4)
        np.testing.assert_array_equal(np.asarray(jax.jit(hop)(x)),
                                      np.asarray(x))

    # the unet368 payloads' shapes with 1/1840 of their rows: exponents of
    # 64/128/256 channels, 64-channel mantissas, and a dense mantissa; one
    # size that is not a multiple of 128; one 1-D array
    HOP_SHAPES = [(96, 2), (96, 4), (96, 8), (96, 64), (37, 3), (96, 256),
                  (1000,)]

    @pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32])
    @pytest.mark.parametrize("shape", HOP_SHAPES)
    def test_offchip_hop_on_tpu_repacks_bit_exactly(self, monkeypatch, shape,
                                                     dtype):
        monkeypatch.setattr(
            jax, "devices",
            lambda *a, **k: [_FakeTpu(["device", "pinned_host"])])
        hop = _make_offchip_hop()
        key = jax.random.PRNGKey(sum(shape))
        x = (jax.random.randint(key, shape, -128, 128, jnp.int8)
             if dtype == jnp.int8 else jax.random.normal(key, shape, dtype))
        y = jax.jit(hop)(x)
        assert y.shape == x.shape and y.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(y).view(np.uint8),
                                      np.asarray(x).view(np.uint8))

    @pytest.mark.parametrize("shape", HOP_SHAPES)
    def test_offchip_hop_sends_lane_dense_arrays_to_host(self, monkeypatch,
                                                         shape):
        """Lowered for a TPU, the one array the hop places in host memory
        has a minor dimension of whole 128-lane rows."""
        import re
        monkeypatch.setattr(
            jax, "devices",
            lambda *a, **k: [_FakeTpu(["device", "pinned_host"])])
        hop = _make_offchip_hop()
        x = jax.ShapeDtypeStruct(shape, jnp.int8)
        text = jax.jit(hop).trace(x).lower(
            lowering_platforms=("tpu",)).as_text()
        sent = re.findall(r'_xla_buffer_placement = "pinned_host"\}\} : '
                          r"\(tensor<([\dx]+)xi8>\)", text)
        assert len(sent) == 1, text
        assert int(sent[0].split("x")[-1]) % 128 == 0, sent

    def test_interpret_refused_for_pallas_on_tpu(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="interpret"):
            resolve_kernel_mode("pallas", True)
        assert resolve_kernel_mode("pallas", None) == (True, False)
        assert resolve_kernel_mode("auto", None) == (True, False)
        # the reference bodies never interpret anything: nothing to refuse
        assert resolve_kernel_mode("reference", True) == (False, True)

    def test_placement_is_explicit(self):
        from repro.api import CompileSpec
        from repro.runtime.streamer import lower_plan_pipelined
        g = build_unet_exec()
        plan, _ = _dse_plan(g)
        assert CompileSpec(model=g).placement == "interleave"
        sx = lower_plan_pipelined(g, plan, microbatches=2,
                                  kernel_mode="reference")
        assert sx.placement == "interleave"
        with pytest.raises(ValueError, match="placement"):
            lower_plan_pipelined(g, plan, microbatches=2,
                                 kernel_mode="reference", placement="auto")

    def test_vertex_body_names_plain_dot_fallback(self):
        """A fully streamed weight of at most 128 rows has no dynamic
        region and runs as a plain dot; a wider one runs the Pallas
        fragmentation kernel."""
        g = build_unet_exec(levels=4)
        plan = ExecutionPlan(
            model=g.name, device="tiny", n_stages=1,
            layers={n: LayerPlan(name=n, weight_static_fraction=0.0)
                    for n in g.topo()},
            streams=[StreamPlan(e.src, e.dst) for e in g.edges()],
            topo_order=g.topo())
        an = analyze_plan(g, plan, use_pallas=True, interpret=True)
        seen = set()
        for n in an.topo:
            v = g.vertex(n)
            body = vertex_body(g, n, an)
            if v.kind in ("conv", "deconv"):
                wide = v.meta["exec"]["cin"] > 128
                assert body == ("pallas" if wide else "reference"), n
                seen.add(wide)
            elif v.kind in ("act", "pool"):
                assert body == "pallas", n
            else:
                assert body == "reference", n
        assert seen == {True, False}
        an_ref = analyze_plan(g, plan, use_pallas=False, interpret=True)
        assert {vertex_body(g, n, an_ref) for n in an.topo} == {"reference"}


class TestCompileCache:
    def test_env_var_wins(self, monkeypatch):
        from repro.compile_cache import ENV_VAR, enable_compile_cache
        monkeypatch.setenv(ENV_VAR, "cache-from-env")
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == "cache-from-env"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_repo_directory(self, monkeypatch):
        import pathlib
        from repro.compile_cache import (DEFAULT_DIR, ENV_VAR,
                                         enable_compile_cache)
        monkeypatch.delenv(ENV_VAR, raising=False)
        repo = pathlib.Path(__file__).resolve().parent.parent
        assert DEFAULT_DIR == repo / ".jax_cache"
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
        before = jax.config.jax_compilation_cache_dir
        try:
            assert enable_compile_cache() == str(DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
