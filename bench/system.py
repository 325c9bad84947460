"""The system under test, built from a configuration's ``system`` block.

This is the one module of the benchmark that imports the program
(``src/repro``).  It compiles the configured graph through the compile
façade, as a user would, checks that the program's graph has the
reference's weights by name and shape, and hands it the benchmark's
weights in place of its own.
"""
from __future__ import annotations

import pathlib
import sys

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def _repro():
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import repro
    return repro


def enable_compile_cache() -> str:
    """The program's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR``
    or the fixed ``.jax_cache`` inside the checkout."""
    _repro()
    from repro.compile_cache import enable_compile_cache as enable
    return enable()


def _tuples(kw: dict) -> dict:
    """JSON lists as the tuples the program's signatures take."""
    return {k: tuple(v) if isinstance(v, list) else v for k, v in kw.items()}


def graph(cfg: dict):
    """The program's graph of the configuration, from its builder."""
    _repro()
    from repro.core import builders
    return getattr(builders, cfg["system"]["builder"])(
        **_tuples(cfg["model_kwargs"]))


def build(cfg: dict, weights: dict):
    """``repro.compile`` of the configuration, pipelined over its
    microbatches, serving ``weights``.  Returns the ``Compiled`` artifact."""
    repro = _repro()
    from repro.core.dse import DSEConfig
    from repro.core.resources import get_device

    s = cfg["system"]
    g = graph(cfg)
    dse = DSEConfig(**_tuples(s["dse"]))
    c = repro.compile(repro.CompileSpec(
        model=g, device=get_device(s["device_view"]), strategy="dse",
        mode="pipelined", kernel_mode=s["kernel_mode"], dse=dse,
        microbatches=s["microbatches"]))
    params = c.executor.params
    theirs = {n: tuple(p.shape) for n, p in params.items()}
    ours = {n: tuple(w.shape) for n, w in weights.items()}
    if theirs != ours:
        raise RuntimeError(f"the program's weights {theirs} are not the "
                           f"reference's {ours}")
    c.executor.params = dict(weights)
    return c


def plan_lines(c) -> list[str]:
    """The plan's evicted and fragmented edges, as report lines."""
    plan = c.plan
    ev = [s for s in plan.streams if s.evicted]
    frag = sorted(n for n, lp in plan.layers.items()
                  if lp.weight_static_fraction < 1.0)
    return [f"plan: {plan.n_stages} stage(s); {len(ev)} evicted edges "
            f"({sum(s.codec == 'bfp8' for s in ev)} bfp8): "
            + ", ".join(f"{s.src}->{s.dst}[{s.codec}]" for s in ev),
            f"plan: {len(frag)} fragmented weights: {', '.join(frag)}"]


def step_hlo(c, net: list[dict]) -> str | None:
    """The compiled HLO text of the pipelined step the window drove, from
    the compile cache, or None where the step is not a jitted function (a
    test's stand-in).  A trace names its device ops by its instructions.
    The step takes ``(microbatches,) + input_shape(net)`` frames."""
    import jax
    import jax.numpy as jnp

    from bench.reference import input_shape
    fn = c.executor.fn
    if not hasattr(fn, "lower"):
        return None
    xs = jax.ShapeDtypeStruct((c.executor.microbatches,) + input_shape(net),
                              jnp.float32)
    return fn.lower(c.executor.params, xs).compile().as_text()
