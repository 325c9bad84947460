"""The plain reference: the benchmark's own forward pass, weights and frames.

Nothing here imports the system under test.  A model is the layer list of
``bench/models/<model>.py``; ``forward`` interprets it in the arithmetic
the configuration states (its ``arithmetic`` block):

- ``matmul_inputs``: the type both inputs of every matmul are rounded to
  before the products, which are summed in float32.  ``"bfloat16"`` is one
  bfloat16 pass, what a float32 dot at JAX's default precision does on a
  TPU; ``"float32"`` is exact float32 products.
- ``storage``: the type every weight, frame and layer output is held in.
- ``bfp8_edges``: the ``[producer, consumer]`` edges whose value reaches
  the consumer through block floating point (``bfp8``): per position, each
  block of ``bfp8_block`` channels shares the exponent
  ``e = ceil(log2(max |x|))`` (0 for an all-zero block) and each value keeps
  a signed 8-bit mantissa ``round(x / 2**(e - 6))``, rounded half to even
  and clipped to [-127, 127].

The weights and the frames are made here, on the device, from the run's
seed, and the system under test is handed the same weights.  The control
(``control_arithmetic``) is the stated arithmetic with everything held in
bfloat16, the precision below the stated float32 storage.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp

WEIGHT_KINDS = ("conv", "deconv")
MODELS_DIR = pathlib.Path(__file__).resolve().parent / "models"


def model_layers(cfg: dict) -> list[dict]:
    """The layer list of a configuration (``cfg["model"]`` names the file
    under ``bench/models/``, ``cfg["model_kwargs"]`` its sizes)."""
    path = MODELS_DIR / f"{cfg['model']}.py"
    spec = importlib.util.spec_from_file_location(f"bench_model_{cfg['model']}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.layers(**cfg["model_kwargs"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` keeps 32)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def weight_shapes(net: list[dict]) -> dict[str, tuple[int, int]]:
    return {L["name"]: (L["cin"], L["cout"]) for L in net
            if L["kind"] in WEIGHT_KINDS}


def input_shape(net: list[dict]) -> tuple[int, int]:
    L = net[0]
    assert L["kind"] == "input", L
    return L["m"], L["cin"]


def make_weights(net: list[dict], key: jax.Array) -> dict[str, jax.Array]:
    """Every weight, N(0, 1/cin), float32, in one jitted call."""
    shapes = sorted(weight_shapes(net).items())

    @jax.jit
    def make(key):
        return {name: jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32) / math.sqrt(shape[0])
                for i, (name, shape) in enumerate(shapes)}
    return make(key)


def make_frames(net: list[dict], key: jax.Array, lead: tuple[int, ...],
                channels: int) -> jax.Array:
    """``lead + (m, cin)`` frames in one jitted call: N(0, 1) on the first
    ``channels`` channels, zeros on the rest (an RGB frame padded to the
    lanes the model takes)."""
    m, cin = input_shape(net)

    @jax.jit
    def make(key):
        x = jax.random.normal(key, lead + (m, channels), jnp.float32)
        pad = [(0, 0)] * (len(lead) + 1) + [(0, cin - channels)]
        return jnp.pad(x, pad)
    return make(key)


def control_arithmetic(arith: dict) -> dict:
    """The control's arithmetic: the stated one, held in bfloat16."""
    return dict(arith, storage="bfloat16")


def bfp8_parts(x: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """``x (m, c)`` as block floating point (module doc): the mantissas
    ``(m, c)`` and the exponents ``(m, c // block)``, both as float32."""
    m, c = x.shape
    xb = x.reshape(m, c // block, block)
    frac, e = jnp.frexp(jnp.max(jnp.abs(xb), axis=-1))
    e = jnp.where(frac == 0.5, e - 1, e)      # ceil(log2(.)) of a power of 2
    step = jnp.ldexp(jnp.float32(1.0), e - 6)
    man = jnp.clip(jnp.round(xb / step[..., None]), -127, 127)
    return man.reshape(m, c), e.astype(jnp.float32)


def bfp8(x: jax.Array, block: int) -> jax.Array:
    """``x (m, c)`` through block floating point and back."""
    m, c = x.shape
    man, e = bfp8_parts(x, block)
    step = jnp.ldexp(jnp.float32(1.0), e.astype(jnp.int32) - 6)
    return (man.reshape(m, c // block, block) * step[..., None]).reshape(m, c)


def forward(net: list[dict], weights: dict, x: jax.Array,
            arith: dict) -> jax.Array:
    """One frame ``(m, cin)`` -> the flat output vector, in ``arith``."""
    mm = jnp.dtype(arith["matmul_inputs"])
    store = jnp.dtype(arith["storage"])

    def held(v):
        return v.astype(store).astype(jnp.float32)

    via_bfp8 = {tuple(e) for e in arith["bfp8_edges"]}
    unknown = via_bfp8 - {(i, L["name"]) for L in net for i in L["inputs"]}
    if unknown:
        raise ValueError(f"bfp8 edges {sorted(unknown)} are not in the model")
    vals: dict[str, jax.Array] = {}
    y = x
    for L in net:
        ins = [bfp8(vals[i], arith["bfp8_block"])
               if (i, L["name"]) in via_bfp8 else vals[i]
               for i in L["inputs"]]
        kind = L["kind"]
        if kind == "input":
            y = x
        elif kind in WEIGHT_KINDS:
            y = jnp.dot(ins[0].astype(mm), held(weights[L["name"]]).astype(mm),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
        elif kind == "act":
            y = jnp.maximum(ins[0], 0.0)
        elif kind == "pool":
            y = ins[0].reshape(L["m_out"], L["m"] // L["m_out"],
                               L["cin"]).mean(axis=1)
        elif kind == "upsample":
            y = jnp.repeat(ins[0], L["m_out"] // L["m"], axis=0)
        elif kind == "concat":
            y = jnp.concatenate(ins, axis=1)
        elif kind == "output":
            y = jnp.concatenate([i.ravel() for i in ins])
        else:
            raise ValueError(f"{L['name']}: unknown kind {kind!r}")
        vals[L["name"]] = held(y)
    return vals[net[-1]["name"]]


def rel_l2_fn(net: list[dict], arith: dict):
    """A jitted ``(weights, x, y) -> ||y - ref(x)|| / ||ref(x)||`` for one
    frame; a non-finite ``y`` reads ``inf``."""
    @jax.jit
    def rel(weights, x, y):
        ref = forward(net, weights, x, arith)
        err = jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref)
        return jnp.where(jnp.all(jnp.isfinite(y)), err, jnp.inf)
    return rel


def control_fn(net: list[dict], arith: dict):
    """The control put in the system's place: ``(weights, xs) -> ys`` for a
    ``(B, m, cin)`` batch, in the control's arithmetic."""
    ctl = control_arithmetic(arith)
    return jax.jit(jax.vmap(lambda w, x: forward(net, w, x, ctl),
                            in_axes=(None, 0)))
