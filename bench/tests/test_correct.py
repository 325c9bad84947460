"""The comparison that decides ``correct``, driven through a whole run.

These runs skip the look for a chip and use a small UNet on the CPU, where
a float32 dot is exact and the DSE evicts nothing at this size.  A sound
system reads correct; the control (the reference with everything held in
bfloat16, put in the system's place) and each fault the cells can have,
planted in the timed path, read not correct.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((ROOT / "bench/configs/unet368.json").read_text())
CFG["model_kwargs"] = {"positions": 256, "cin": 32, "base": 32, "levels": 3,
                       "n_classes": 32}
CFG["system"] = dict(CFG["system"], kernel_mode="reference", microbatches=4)
CFG["arithmetic"] = dict(CFG["arithmetic"], matmul_inputs="float32",
                         bfp8_edges=[])
TRAFFIC = json.loads((ROOT / "bench/traffic/stream.json").read_text())
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(wrap=None, seed: int = 2 ** 31 + 11) -> dict:
    # named as the cell of BENCHMARK.json, so the run reports its metrics
    cell = {"name": "unet368.stream", "config": "unet368",
            "traffic": "stream", "chips": 1}
    return run.run_cell(BENCH, cell, copy.deepcopy(CFG), TRAFFIC,
                        seed=seed, seconds=0.6, trace=False, device=DEVICE,
                        peaks={}, wrap=wrap)


def _wrap_fn(make):
    """Replace the pipelined step ``fn(params, xs)`` by ``make(fn)``."""
    def wrap(compiled):
        ex = compiled.executor
        ex.fn = make(ex.fn)
    return wrap


def _stale(fn):
    first = {}

    def f(params, xs):
        if "y" not in first:
            first["y"] = fn(params, xs)
        return first["y"]
    return f


def _half_batch(fn):
    def f(params, xs):
        ys = fn(params, xs)
        h = ys.shape[0] // 2
        return jnp.concatenate([ys[h:2 * h], ys[h:]])
    return f


def _altered(fn):
    def f(params, xs):
        ys = fn(params, xs)
        return ys.at[0].multiply(1.5)
    return f


def _swapped(fn):
    def f(params, xs):
        ys = fn(params, xs)
        return ys[jnp.array([1, 0] + list(range(2, ys.shape[0])))]
    return f


def _control(fn):
    net = reference.model_layers(CFG)
    ctl = reference.control_fn(net, CFG["arithmetic"])
    return lambda params, xs: ctl(params, xs)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["frame_rel_l2"]["frames"] > 0
    want = {m["name"]: m["unit"] for m in BENCH["end_to_end"]
            if run.applies(m, {"name": "unet368.stream"})}
    assert "setup_s" in want
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("fault", [_stale, _half_batch, _altered, _swapped,
                                   _control])
def test_broken_timed_path_is_not_correct(fault):
    res = _run(wrap=_wrap_fn(fault))
    assert not res["correct"], res["checks"]


SKIPS = [["act_3", "concat_17"], ["act_6", "concat_12"]]


def _evicting(compiled):
    """The same program on a plan that evicts the small UNet's two long
    skips through BFP8, as the DSE does at the cells' size."""
    import repro
    plan = copy.deepcopy(compiled.plan)
    for st in plan.streams:
        if [st.src, st.dst] in SKIPS:
            st.evicted, st.codec = True, "bfp8"
    lossy = repro.compile(dataclasses.replace(
        compiled.spec, strategy="manual-plan", plan=plan))
    assert sum(st.codec == "bfp8" for st in lossy.plan.streams) == 2
    compiled.executor.fn = lossy.executor.fn


@pytest.mark.parametrize("stated,correct", [(SKIPS, True), ([], False)])
def test_evicted_skips_are_held_to_the_stated_codec(stated, correct):
    """The reference's BFP8, written from the format, is the program's: a
    run that evicts the skips reads correct against the reference that
    states them, and not correct against one that states no codec."""
    global CFG
    saved = CFG
    CFG = dict(CFG, arithmetic=dict(CFG["arithmetic"], bfp8_edges=stated))
    try:
        res = _run(wrap=_evicting)
    finally:
        CFG = saved
    assert res["correct"] is correct, res["checks"]


def test_seed_key_keeps_all_64_bits():
    a = reference.seed_key(1)
    b = reference.seed_key(2 ** 32 + 1)
    assert not bool(jnp.all(jax.random.key_data(a) == jax.random.key_data(b)))
