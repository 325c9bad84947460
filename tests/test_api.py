"""Compile façade tests (ISSUE 4 tentpole): ``CompileSpec`` -> ``Compiled``.

The acceptance contract: ``repro.compile`` succeeds for every registered
exec model x mode, its ``.run`` output is *bit-identical* to calling the
pre-façade lowering functions directly, and a ``Compiled.save``d artifact
reloads and runs — bit-identically — in a fresh process.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import CompileSpec, Compiled
from repro.core import (DSEConfig, EXEC_MODELS, build_unet_exec,
                        exec_input_shape, get_model, plan_from_dse, run_dse)
from repro.core.plan import PLAN_SCHEMA_VERSION
from repro.core.resources import Device

# the memory-starved streaming device view the e2e benchmark uses: forces
# the DSE into eviction + fragmentation on every exec graph
TINY = Device("tiny_stream", compute_units=4096, onchip_bits=300_000,
              offchip_gbps=64.0, freq_mhz=500.0, reconfig_s=0.0)
DSE_CFG = DSEConfig(batch=1, codecs=("none", "bfp8"), word_bits=16,
                    cut_kinds=("pool", "conv"))


def _spec(name, **kw):
    kw.setdefault("device", TINY)
    kw.setdefault("strategy", "dse")
    kw.setdefault("dse", DSE_CFG)
    kw.setdefault("kernel_mode", "reference")
    return CompileSpec(model=name, **kw)


def _input(compiled, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             compiled.input_shape(), jnp.float32)


class TestParity:
    """compile(mode=...) == the direct lowering path, bit for bit, for
    every model in EXEC_MODELS (the acceptance matrix)."""

    @pytest.mark.parametrize("name", sorted(EXEC_MODELS))
    def test_staged_matches_lower_plan(self, name):
        from repro.runtime.executor import lower_plan
        c = repro.compile(_spec(name, mode="staged"))
        g = get_model(name, EXEC_MODELS)()
        res = run_dse(g, TINY, DSE_CFG)
        plan = plan_from_dse(name, TINY.name, res)
        low = lower_plan(g, plan, kernel_mode="reference")
        x = _input(c)
        np.testing.assert_array_equal(np.asarray(c.run(x)),
                                      np.asarray(low(x)))

    @pytest.mark.parametrize("name", sorted(EXEC_MODELS))
    def test_pipelined_matches_lower_plan_pipelined(self, name):
        from repro.runtime.streamer import lower_plan_pipelined
        c = repro.compile(_spec(name, mode="pipelined", microbatches=2))
        g = get_model(name, EXEC_MODELS)()
        res = run_dse(g, TINY, DSE_CFG)
        plan = plan_from_dse(name, TINY.name, res)
        sx = lower_plan_pipelined(g, plan, microbatches=2,
                                  kernel_mode="reference")
        x = _input(c)
        xs = jnp.stack([x, 2.0 * x])
        np.testing.assert_array_equal(np.asarray(c.run(xs)),
                                      np.asarray(sx(xs)))

    def test_reference_matches_reference_pipeline(self):
        from repro.runtime.executor import reference_pipeline
        c = repro.compile(_spec("unet_exec", mode="reference"))
        x = _input(c)
        want = reference_pipeline(get_model("unet_exec", EXEC_MODELS)())(x)
        np.testing.assert_array_equal(np.asarray(c.run(x)),
                                      np.asarray(want))
        assert c.plan is None            # the baseline is plan-free

    def test_pipelined_single_frame_convenience(self):
        c = repro.compile(_spec("unet_exec", mode="pipelined",
                                microbatches=2))
        x = _input(c)
        y1 = c.run(x)                                   # (L,)
        ys = c.run(jnp.broadcast_to(x, (2,) + x.shape))  # (2, L)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(ys[0]))


class TestSpec:
    def test_unknown_mode_and_strategy_rejected(self):
        with pytest.raises(ValueError, match="mode"):
            repro.compile(_spec("unet_exec", mode="warp"))
        with pytest.raises(ValueError, match="strategy"):
            repro.compile(_spec("unet_exec", strategy="oracle"))

    def test_manual_plan_requires_plan(self):
        with pytest.raises(ValueError, match="manual-plan"):
            repro.compile(CompileSpec(model="unet_exec",
                                      strategy="manual-plan", mode="staged"))

    def test_unknown_model_lists_registry(self):
        with pytest.raises(KeyError, match="unet_exec"):
            repro.compile(_spec("resnet9000"))

    def test_use_pallas_shorthand(self):
        assert CompileSpec(model="m", use_pallas=True
                           ).resolved_kernel_mode() == "pallas"
        assert CompileSpec(model="m", use_pallas=False, kernel_mode="pallas"
                           ).resolved_kernel_mode() == "reference"
        assert CompileSpec(model="m", kernel_mode="auto"
                           ).resolved_kernel_mode() == "auto"
        c = repro.compile(_spec("unet_exec", mode="staged",
                                kernel_mode="auto", use_pallas=False))
        x = _input(c)
        want = repro.compile(_spec("unet_exec", mode="staged")).run(x)
        np.testing.assert_array_equal(np.asarray(c.run(x)), np.asarray(want))

    def test_graph_instance_model(self):
        g = build_unet_exec(positions=32, levels=2)
        c = repro.compile(_spec(g, mode="staged"))
        assert c.model == "unet_exec"
        assert c.input_shape() == exec_input_shape(g)


class TestProvenanceAndReport:
    def test_plan_provenance_stamped(self):
        c = repro.compile(_spec("unet_exec", mode="staged"))
        prov = c.plan.provenance
        assert prov["strategy"] == "dse"
        assert prov["device"] == "tiny_stream"
        assert prov["compiled_by"] == "repro.api.compile"
        assert c.plan.schema_version == PLAN_SCHEMA_VERSION

    def test_unified_report(self):
        c = repro.compile(_spec("unet_exec", mode="pipelined",
                                microbatches=2))
        rep = c.report()
        assert rep["model"] == "unet_exec"
        assert rep["mode"] == "pipelined"
        assert rep["strategy"] == "dse"
        assert rep["traffic"]["n_stages"] == c.plan.n_stages
        assert "total_offchip_bits" in rep["traffic"]
        assert rep["provenance"]["device"] == "tiny_stream"

    def test_autotune_strategy_provenance_and_report(self):
        from repro.optim.autotune import AutotuneConfig
        g = build_unet_exec(positions=32, levels=2)
        c = repro.compile(CompileSpec(
            model=g, device=TINY, strategy="autotune", mode="pipelined",
            autotune_cfg=AutotuneConfig(n_candidates=2, microbatches=2,
                                        repeats=1, warmup=1,
                                        kernel_mode="reference"),
            kernel_mode="reference"))
        assert c.autotune_result is not None
        prov = c.plan.provenance
        assert prov["strategy"] == "autotune"
        assert len(prov["autotune_digest"]) == 16
        assert prov["s_per_cycle"] > 0
        assert prov["best_fps"] >= prov["baseline_fps"]
        rep = c.report()
        assert rep["autotune"]["candidates"] == 2
        assert "calibration" in rep["autotune"]
        # the executor serves at the depth the search measured at
        assert c.executor.microbatches == 2
        # ...and a serve() with overrides keeps that depth unless the
        # caller explicitly changes it
        srv = c.serve(seed=0)                # any override forces re-lower
        assert srv.microbatches == 2


class TestServe:
    def test_serve_reuses_pipelined_executor(self):
        c = repro.compile(_spec("unet_exec", mode="pipelined",
                                microbatches=2))
        srv = c.serve()
        assert srv.executor is c.executor
        x = np.asarray(_input(c))
        t0, t1 = srv.submit(x), srv.submit(2.0 * x)
        out = srv.flush()
        np.testing.assert_array_equal(out[t0], np.asarray(c.run(x)))
        assert set(out) == {t0, t1}

    def test_serve_rejects_plan_free_reference(self):
        c = repro.compile(_spec("unet_exec", mode="reference"))
        with pytest.raises(ValueError, match="plan-free"):
            c.serve()

    def test_serve_relower_from_staged(self):
        c = repro.compile(_spec("unet_exec", mode="staged"))
        srv = c.serve(microbatches=2)
        assert srv.microbatches == 2
        assert srv.executor.plan is c.plan   # same decisions, re-lowered

    def test_stream_server_legacy_signature_still_works(self):
        from repro.serving.engine import GraphStreamServer
        c = repro.compile(_spec("unet_exec", mode="staged"))
        g = get_model("unet_exec", EXEC_MODELS)()
        srv = GraphStreamServer(g, c.plan, microbatches=2,
                                kernel_mode="reference")
        x = np.asarray(_input(c))
        t = srv.submit(x)
        np.testing.assert_array_equal(srv.flush()[t], np.asarray(c.run(x)))


class TestSaveLoad:
    def test_roundtrip_bit_identical_in_process(self, tmp_path):
        c = repro.compile(_spec("unet_exec", mode="staged"))
        path = c.save(tmp_path / "unet.smof.json")
        d = json.loads(path.read_text())
        assert d["artifact"] == "smof-compiled"
        assert d["plan_schema_version"] == PLAN_SCHEMA_VERSION
        assert d["plan"]["provenance"]["strategy"] == "dse"
        back = Compiled.load(path)
        assert back.spec.strategy == "manual-plan"   # decisions are baked in
        x = _input(c, seed=7)
        np.testing.assert_array_equal(np.asarray(back.run(x)),
                                      np.asarray(c.run(x)))

    def test_custom_graph_roundtrip(self, tmp_path):
        # the artifact embeds the graph, so non-default builder kwargs
        # (which the registry could not reproduce) reload exactly
        g = build_unet_exec(positions=32, levels=2)
        c = repro.compile(_spec(g, mode="pipelined", microbatches=2))
        back = Compiled.load(c.save(tmp_path / "small.smof.json"))
        assert back.input_shape() == exec_input_shape(g)
        x = _input(c, seed=3)
        np.testing.assert_array_equal(np.asarray(back.run(x)),
                                      np.asarray(c.run(x)))

    def test_auto_placement_artifact_reloads_interleaved(self, tmp_path):
        # older artifacts saved placement="auto"; it reloads as the
        # one-device scan, never as the multi-device ring
        g = build_unet_exec(positions=32, levels=2)
        c = repro.compile(_spec(g, mode="pipelined", microbatches=2))
        path = c.save(tmp_path / "auto.smof.json")
        d = json.loads(path.read_text())
        assert d["placement"] == "interleave"
        d["placement"] = "auto"
        path.write_text(json.dumps(d))
        back = Compiled.load(path)
        assert back.spec.placement == "interleave"
        assert back.executor.placement == "interleave"

    def test_newer_artifact_schema_rejected(self, tmp_path):
        c = repro.compile(_spec("unet_exec", mode="staged"))
        path = c.save(tmp_path / "a.json")
        d = json.loads(path.read_text())
        d["artifact_schema_version"] = 99
        path.write_text(json.dumps(d))
        with pytest.raises(ValueError, match="newer"):
            Compiled.load(path)
        path.write_text(json.dumps({"artifact": "other"}))
        with pytest.raises(ValueError, match="not a smof-compiled"):
            Compiled.load(path)

    def test_fresh_process_reload_bit_identical(self, tmp_path):
        """The acceptance criterion: a saved artifact reloads and runs in a
        *fresh process*, bit-identical (weights are seeded, the graph is
        embedded)."""
        g = build_unet_exec(positions=32, levels=2)
        c = repro.compile(_spec(g, mode="staged"))
        art = c.save(tmp_path / "fresh.smof.json")
        x = _input(c, seed=11)
        want = np.asarray(c.run(x))
        out = tmp_path / "y.npy"
        code = (
            "import numpy as np, jax, jax.numpy as jnp\n"
            "import repro\n"
            f"c = repro.Compiled.load({str(art)!r})\n"
            "x = jax.random.normal(jax.random.PRNGKey(11), c.input_shape(),"
            " jnp.float32)\n"
            f"np.save({str(out)!r}, np.asarray(c.run(x)))\n")
        src = pathlib.Path(__file__).resolve().parent.parent / "src"
        env = dict(os.environ,
                   PYTHONPATH=f"{src}{os.pathsep}"
                              f"{os.environ.get('PYTHONPATH', '')}",
                   JAX_PLATFORMS="cpu")
        subprocess.run([sys.executable, "-c", code], check=True, env=env,
                       timeout=600)
        np.testing.assert_array_equal(np.load(out), want)


class TestGraphSerialisation:
    def test_operand_order_preserved(self):
        """Multi-input ops consume operands in predecessor order; the
        structural dump must reproduce it (concat is order-sensitive)."""
        from repro.core.graph import Graph
        g = build_unet_exec(positions=32, levels=2)
        g2 = Graph.from_json_dict(g.to_json_dict())
        for n in g.topo():
            assert g.predecessors(n) == g2.predecessors(n)
        assert g2.to_json_dict() == g.to_json_dict()

    def test_design_state_included(self):
        g = build_unet_exec(positions=32, levels=2)
        run_dse(g, TINY, DSE_CFG)            # mutates eviction/frag state
        from repro.core.graph import Graph
        g2 = Graph.from_json_dict(g.to_json_dict())
        assert ([(e.src, e.dst, e.evicted, e.codec) for e in g.edges()]
                == [(e.src, e.dst, e.evicted, e.codec) for e in g2.edges()])


class TestPlanMigration:
    def test_unknown_keys_collected_not_silently_dropped(self):
        from repro.core.plan import ExecutionPlan
        c = repro.compile(_spec("unet_exec", mode="staged"))
        d = json.loads(c.plan.to_json())
        lname = next(iter(d["layers"]))
        d["from_the_future"] = 1
        d["layers"][lname]["future_knob"] = 2
        d["streams"][0]["future_flag"] = True
        back = ExecutionPlan.from_json(json.dumps(d))
        assert set(back.dropped_keys) == {
            "plan.from_the_future", f"layers[{lname}].future_knob",
            "streams[0].future_flag"}
        assert back.layers.keys() == c.plan.layers.keys()
        assert back.streams == c.plan.streams

    def test_v1_plans_migrate(self):
        from repro.core.plan import ExecutionPlan
        c = repro.compile(_spec("unet_exec", mode="staged"))
        d = json.loads(c.plan.to_json())
        del d["schema_version"]              # what a v1 writer produced
        del d["provenance"]
        back = ExecutionPlan.from_json(json.dumps(d))
        # migrated forward to the current shape, observably
        assert back.schema_version == PLAN_SCHEMA_VERSION
        assert back.provenance == {"migrated_from_schema_version": 1}
        assert back.dropped_keys == ()
        # re-serialising a migrated plan emits a current-schema payload
        again = ExecutionPlan.from_json(back.to_json())
        assert again.schema_version == PLAN_SCHEMA_VERSION
        assert again.to_json() == back.to_json()

    def test_save_load_save_strategy_stable(self, tmp_path):
        c = repro.compile(_spec("unet_exec", mode="staged"))
        p1 = c.save(tmp_path / "a.json")
        back = Compiled.load(p1)
        assert back.strategy == "dse"        # decision origin survives
        assert back.report()["strategy"] == "dse"
        p2 = back.save(tmp_path / "b.json")
        assert (json.loads(p2.read_text())["strategy"]
                == json.loads(p1.read_text())["strategy"] == "dse")
