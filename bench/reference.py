"""The plain reference: the benchmark's own forward pass, weights and frames.

Nothing here imports the system under test.  A model is the layer list of
``bench/models/<model>.py``: one dict per layer with its ``name``,
``kind``, ``inputs`` (the names of the layers it reads, in order), ``cin``
and ``cout``.  ``forward`` interprets it.

A layer without ``shape`` is a 1-D layer of ``m`` positions (``m_out``
out) and its activations are ``(m, c)``: ``conv`` and ``deconv`` are
``(m, cin) @ (cin, cout)``, ``pool`` is the mean over groups of
``m // m_out`` adjacent positions, ``upsample`` repeats each position
``m_out // m`` times.

A layer with ``shape`` is spatial: ``shape`` is its input's extent,
``[H, W]`` or ``[T, H, W]``, ``shape_out`` its output's where that
differs, and an activation is ``shape + [c]``, channels last.  ``k`` and
``stride`` are an int or one int per spatial dim.

- ``conv``: kernel ``k``, ``stride`` (1), ``groups`` (1; ``cin`` is
  depthwise) and zero padding ``k // 2`` per side, as PyTorch's
  ``padding=k//2``: the output extent is ``floor((n + 2p - k) / s) + 1``.
  The weight is ``k... x cin/groups x cout``; where ``bias`` is true,
  ``<name>.bias`` of ``(cout,)`` is added to the sums.
- ``deconv``: the transposed conv with ``k = stride`` and no padding: the
  output extent is ``n * s``; the weight is ``k... x cin x cout``.
- ``pool``: ``op`` ``max`` or ``mean`` over windows ``k`` at ``stride``
  (``k``) with padding ``pad`` (0) per side: ``-inf`` for max, zeros for
  mean, which divides by the whole window.  A ``shape_out`` of all ones is
  the global ``op`` over the extent.
- ``upsample``: nearest neighbour, by the integer ratio
  ``shape_out / shape``.

Any layer:

- ``input``; ``act``: ``fn`` ``relu`` (the default), ``silu`` or
  ``sigmoid``; ``add`` and ``mul`` of all inputs, where an operand whose
  spatial dims are all 1 broadcasts; ``slice``: channels ``[lo, hi)``;
  ``concat``: along the channels; ``output``: the inputs flattened and
  concatenated.

The arithmetic is the configuration's ``arithmetic`` block:

- ``matmul_inputs``: the type both inputs of every conv and deconv are
  rounded to before the products, which are summed in float32.
  ``"bfloat16"`` is one bfloat16 pass, what a float32 dot at JAX's default
  precision does on a TPU; ``"float32"`` is exact float32 products.
- ``storage``: the type every weight, frame and layer output is held in.
- ``bfp8_edges``: the ``[producer, consumer]`` edges whose value reaches
  the consumer through block floating point (``bfp8``): at each position,
  the channels fall into blocks of ``bfp8_block``, the last one shorter
  where the channels are not a multiple of it; each block shares the
  exponent ``e = ceil(log2(max |x|))`` of its own channels (0 for an
  all-zero block) and each value keeps a signed 8-bit mantissa
  ``round(x / 2**(e - 6))``, rounded half to even and clipped to
  [-127, 127].

The weights and the frames are made here, on the device, from the run's
seed, and the system under test is handed the same weights.  The control
(``control_arithmetic``) is the stated arithmetic with everything held in
bfloat16, the precision below the stated float32 storage.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp

WEIGHT_KINDS = ("conv", "deconv")
MODELS_DIR = pathlib.Path(__file__).resolve().parent / "models"
SPATIAL_DIMS = "DHW"            # conv dimension letters of 1-3 spatial dims
HIGHEST = jax.lax.Precision.HIGHEST


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


def per_dim(L: dict, key: str, default: int | None = None) -> tuple[int, ...]:
    """``L[key]`` (an int or one per spatial dim) as one int per dim."""
    v = L.get(key, default)
    if v is None:
        raise ValueError(f"{L['name']}: {L['kind']} needs {key!r}")
    nd = len(L["shape"])
    v = (v,) * nd if isinstance(v, int) else tuple(v)
    if len(v) != nd:
        raise ValueError(f"{L['name']}: {key} {v} for {nd} spatial dims")
    return v


def is_global(L: dict) -> bool:
    """Whether a spatial pool is the global one: ``shape_out`` all ones
    and no window."""
    return set(L.get("shape_out", ())) == {1} and "k" not in L


def out_shape(L: dict) -> tuple[int, ...]:
    """The output extent of a spatial layer, from its kind and geometry;
    a ``shape_out`` that disagrees is refused."""
    shape = tuple(L["shape"])
    stated = tuple(L["shape_out"]) if "shape_out" in L else None
    kind = L["kind"]
    if kind == "pool" and is_global(L):
        out = stated
    elif kind in ("conv", "pool"):
        k = per_dim(L, "k")
        s = per_dim(L, "stride", 1 if kind == "conv" else L["k"])
        p = (tuple(kk // 2 for kk in k) if kind == "conv"
             else per_dim(L, "pad", 0))
        out = tuple((n + 2 * pp - kk) // ss + 1
                    for n, kk, ss, pp in zip(shape, k, s, p))
    elif kind == "deconv":
        k = per_dim(L, "k")
        if per_dim(L, "stride", L["k"]) != k:
            raise ValueError(f"{L['name']}: a deconv's stride is its k")
        out = tuple(n * kk for n, kk in zip(shape, k))
    elif kind == "upsample":
        if stated is None or any(o % n for o, n in zip(stated, shape)):
            raise ValueError(f"{L['name']}: {stated} is no whole multiple "
                             f"of {shape}")
        out = stated
    else:
        out = shape
    if stated not in (None, out):
        raise ValueError(f"{L['name']}: shape_out {stated} is not the "
                         f"{kind}'s output extent {out}")
    return out


def weight_shapes(net: list[dict]) -> dict[str, tuple[int, ...]]:
    out = {}
    for L in net:
        if L["kind"] not in WEIGHT_KINDS:
            continue
        if "shape" not in L:
            out[L["name"]] = (L["cin"], L["cout"])
            continue
        cin = L["cin"] // L.get("groups", 1)
        out[L["name"]] = per_dim(L, "k") + (cin, L["cout"])
        if L.get("bias"):
            out[f"{L['name']}.bias"] = (L["cout"],)
    return out


def input_shape(net: list[dict]) -> tuple[int, ...]:
    """One frame's shape: ``(m, cin)``, or ``shape + (cin,)``."""
    L = net[0]
    if L["kind"] != "input":
        raise ValueError(f"the first layer is not the input: {L}")
    if "shape" not in L:
        return L["m"], L["cin"]
    return tuple(L["shape"]) + (L["cin"],)


def make_weights(net: list[dict], key: jax.Array) -> dict[str, jax.Array]:
    """Every weight, N(0, 1/fan-in), float32, in one jitted call.  A
    weight's fan-in is the product of all its dims but the last; a bias
    takes its weight's."""
    shapes = sorted(weight_shapes(net).items())
    fan_in = {name: math.prod(shape[:-1]) for name, shape in shapes}
    fan_in = {name: fan_in[name.removesuffix(".bias")] for name in fan_in}

    @jax.jit
    def make(key):
        return {name: jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
                / math.sqrt(fan_in[name])
                for i, (name, shape) in enumerate(shapes)}
    return make(key)


def make_frames(net: list[dict], key: jax.Array, lead: tuple[int, ...],
                channels: int) -> jax.Array:
    """``lead + input_shape(net)`` frames in one jitted call: N(0, 1) on
    the first ``channels`` channels, zeros on the rest (an RGB frame padded
    to the lanes the model takes)."""
    shape = input_shape(net)

    @jax.jit
    def make(key):
        x = jax.random.normal(key, lead + shape[:-1] + (channels,),
                              jnp.float32)
        pad = [(0, 0)] * (len(lead) + len(shape) - 1)
        return jnp.pad(x, pad + [(0, shape[-1] - channels)])
    return make(key)


def control_arithmetic(arith: dict) -> dict:
    """The control's arithmetic: the stated one, held in bfloat16."""
    return dict(arith, storage="bfloat16")


def bfp8_parts(x: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """``x (..., c)`` as block floating point (module doc): the mantissas
    ``(..., c)`` and the exponents ``(..., ceil(c / block))``, both as
    float32.  A short last block is padded with zeros, which leave its
    largest magnitude, so its exponent, as its own channels give it."""
    c = x.shape[-1]
    whole = -(-c // block) * block
    if whole != c:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, whole - c)])
    xb = x.reshape(x.shape[:-1] + (whole // block, block))
    frac, e = jnp.frexp(jnp.max(jnp.abs(xb), axis=-1))
    e = jnp.where(frac == 0.5, e - 1, e)      # ceil(log2(.)) of a power of 2
    step = jnp.ldexp(jnp.float32(1.0), e - 6)
    man = jnp.clip(jnp.round(xb / step[..., None]), -127, 127)
    return man.reshape(x.shape)[..., :c], e.astype(jnp.float32)


def bfp8(x: jax.Array, block: int) -> jax.Array:
    """``x (..., c)`` through block floating point and back."""
    c = x.shape[-1]
    man, e = bfp8_parts(x, block)
    step = jnp.ldexp(jnp.float32(1.0), e.astype(jnp.int32) - 6)
    return man * jnp.repeat(step, block, axis=-1)[..., :c]


ACTS = {"relu": lambda v: jnp.maximum(v, 0.0),
        "silu": jax.nn.silu,
        "sigmoid": jax.nn.sigmoid}


def _conv(L: dict, x: jax.Array, w: jax.Array) -> jax.Array:
    """A conv of one frame, both inputs as given, summed in float32: a 1-D
    layer's ``(m, cin) @ (cin, cout)``, or a spatial one."""
    if "shape" not in L:
        return jnp.dot(x, w, preferred_element_type=jnp.float32,
                       precision=HIGHEST)
    sp = SPATIAL_DIMS[-len(L["shape"]):]
    return jax.lax.conv_general_dilated(
        x[None], w, window_strides=per_dim(L, "stride", 1),
        padding=[(k // 2, k // 2) for k in per_dim(L, "k")],
        dimension_numbers=(f"N{sp}C", f"{sp}IO", f"N{sp}C"),
        feature_group_count=L.get("groups", 1), precision=HIGHEST,
        preferred_element_type=jnp.float32)[0]


def _deconv(L: dict, x: jax.Array, w: jax.Array) -> jax.Array:
    """A deconv: a 1-D layer's is its conv; in a spatial one, each input
    position's ``x @ W[tap]`` fills its own ``k...`` block of the output."""
    if "shape" not in L:
        return _conv(L, x, w)
    nd = len(L["shape"])
    y = jnp.tensordot(x, w, axes=([nd], [nd]), precision=HIGHEST,
                      preferred_element_type=jnp.float32)
    # (n..., k..., cout) -> (n0, k0, n1, k1, ..., cout) -> (n * k..., cout)
    y = y.transpose([i for d in range(nd) for i in (d, nd + d)] + [2 * nd])
    return y.reshape(out_shape(L) + (L["cout"],))


def _pool(L: dict, x: jax.Array) -> jax.Array:
    if "shape" not in L:
        return x.reshape(L["m_out"], L["m"] // L["m_out"],
                         L["cin"]).mean(axis=1)
    op = L["op"]
    if op not in ("max", "mean"):
        raise ValueError(f"{L['name']}: unknown pool op {op!r}")
    nd = len(L["shape"])
    if is_global(L):
        reduce = jnp.max if op == "max" else jnp.mean
        return reduce(x, axis=tuple(range(nd)), keepdims=True)
    k = per_dim(L, "k")
    init, fn = ((-jnp.inf, jax.lax.max) if op == "max"
                else (0.0, jax.lax.add))
    y = jax.lax.reduce_window(
        x, jnp.float32(init), fn, k + (1,),
        per_dim(L, "stride", L["k"]) + (1,),
        [(p, p) for p in per_dim(L, "pad", 0)] + [(0, 0)])
    return y if op == "max" else y / math.prod(k)


def _upsample(L: dict, x: jax.Array) -> jax.Array:
    if "shape" not in L:
        return jnp.repeat(x, L["m_out"] // L["m"], axis=0)
    for d, (o, n) in enumerate(zip(out_shape(L), L["shape"])):
        x = jnp.repeat(x, o // n, axis=d)
    return x


def forward(net: list[dict], weights: dict, x: jax.Array,
            arith: dict) -> jax.Array:
    """One frame (``input_shape(net)``) -> the flat output vector, in
    ``arith``."""
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
            body = _conv if kind == "conv" else _deconv
            y = body(L, ins[0].astype(mm),
                     held(weights[L["name"]]).astype(mm))
            if L.get("bias"):
                y = y + held(weights[f"{L['name']}.bias"])
        elif kind == "act":
            y = ACTS[L.get("fn", "relu")](ins[0])
        elif kind == "pool":
            y = _pool(L, ins[0])
        elif kind == "upsample":
            y = _upsample(L, ins[0])
        elif kind == "add":
            y = functools.reduce(jnp.add, ins)
        elif kind == "mul":
            y = functools.reduce(jnp.multiply, ins)
        elif kind == "slice":
            y = ins[0][..., L["lo"]:L["hi"]]
        elif kind == "concat":
            y = jnp.concatenate(ins, axis=-1)
        elif kind == "output":
            y = jnp.concatenate([i.ravel() for i in ins])
        else:
            raise ValueError(f"{L['name']}: unknown kind {kind!r}")
        if "shape" in L and kind != "output" \
                and y.shape[:-1] != out_shape(L):
            raise ValueError(f"{L['name']}: gives {y.shape[:-1]}, the layer "
                             f"states {out_shape(L)}")
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
    ``(B,) + input_shape(net)`` batch, in the control's arithmetic."""
    ctl = control_arithmetic(arith)
    return jax.jit(jax.vmap(lambda w, x: forward(net, w, x, ctl),
                            in_axes=(None, 0)))
