"""The ``GraphStreamServer`` front-end that feeds the pipelined streamer:
its flush against the sequential executor and its metrics surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_unet_exec
from repro.core.plan import ExecutionPlan, LayerPlan, StreamPlan
from repro.runtime.executor import lower_plan
from repro.serving.engine import GraphStreamServer


class TestGraphStreamServer:
    def _plan(self, g, n_stages=2):
        topo = g.topo()
        stage = {n: min(i * n_stages // len(topo), n_stages - 1)
                 for i, n in enumerate(topo)}
        layers = {v.name: LayerPlan(name=v.name, stage=stage[v.name])
                  for v in g.vertices()}
        streams = [StreamPlan(e.src, e.dst) for e in g.edges()]
        return ExecutionPlan(model=g.name, device="tiny", n_stages=n_stages,
                             layers=layers, streams=streams, topo_order=topo)

    def test_flush_matches_sequential_executor(self):
        g = build_unet_exec(positions=32, levels=2)
        plan = self._plan(g)
        srv = GraphStreamServer(g, plan, microbatches=4,
                                kernel_mode="reference")
        low = lower_plan(g, plan, kernel_mode="reference")
        frames = [np.asarray(jax.random.normal(jax.random.PRNGKey(i),
                                               (32, 32), jnp.float32))
                  for i in range(6)]            # 1.5 streams -> padding
        tickets = [srv.submit(f) for f in frames]
        out = srv.flush()
        assert sorted(out) == tickets
        for t, f in zip(tickets, frames):
            np.testing.assert_allclose(out[t], np.asarray(low(jnp.asarray(f))),
                                       rtol=1e-5, atol=1e-5)
        assert srv.stats.streams_run == 2
        assert srv.stats.padded_frames == 2      # 6 frames into 2x4 slots
        assert srv.stats.frames_out == 6
        # delivered results are claimable by ticket, exactly once
        np.testing.assert_array_equal(srv.result(tickets[0]), out[tickets[0]])
        with pytest.raises(KeyError):
            srv.result(tickets[0])


class TestMetricsSurface:
    """The stream server exposes a registry-backed scrape surface; its
    stats object is a live view of the same registry."""

    def test_stream_server_metrics_text_round_trips(self):
        from repro.obs import parse_metrics_text
        g = build_unet_exec(positions=32, levels=2)
        g.compute_buffer_depths()
        topo = g.topo()
        plan = ExecutionPlan(
            model=g.name, device="tiny", n_stages=1,
            layers={n: LayerPlan(name=n, stage=0) for n in topo},
            streams=[StreamPlan(e.src, e.dst) for e in g.edges()],
            topo_order=topo)
        srv = GraphStreamServer(g, plan, microbatches=4,
                                kernel_mode="reference")
        for i in range(6):
            srv.submit(np.zeros((32, 32), np.float32))
        srv.flush()
        fams = parse_metrics_text(srv.metrics_text())
        s = {k: v for f in fams.values() for k, v in f["samples"].items()}
        assert s["smof_server_frames_in_total"] == 6.0
        assert s["smof_server_frames_out_total"] == 6.0
        assert s["smof_server_streams_total"] == srv.stats.streams_run == 2.0
        assert s["smof_server_padded_frames_total"] == 2.0
        assert s["smof_server_frame_latency_seconds_count"] == 6.0
        assert srv.stats.report()["smof_server_frames_in_total"] == 6.0
