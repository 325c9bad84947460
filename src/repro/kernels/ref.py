"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def streamed_matmul_ref(x: jax.Array, w_static: jax.Array,
                        w_dyn: jax.Array) -> jax.Array:
    """y = x @ [w_static; w_dyn] — the fragmentation split is semantically
    invisible; only the memory placement differs."""
    w = jnp.concatenate([w_static, w_dyn], axis=0)
    return jnp.dot(x, w, preferred_element_type=jnp.float32
                   ).astype(x.dtype)


def conv2d_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """1x1 channel mixing (conv/matmul/deconv): y = x @ w."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32
                   ).astype(x.dtype)


def dwconv_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise temporal conv, 'same' zero padding; w: (taps, c).  The
    tap sum runs in Python ``sum`` order — the accumulation order the
    streaming_conv kernels replicate for bit parity."""
    taps = w.shape[0]
    pad = taps // 2
    xp = jnp.pad(x, ((pad, taps - 1 - pad), (0, 0)))
    m = x.shape[0]
    return sum(w[k][None, :] * xp[k:k + m] for k in range(taps))


def pool_ref(x: jax.Array, m_out: int) -> jax.Array:
    """Position-axis mean to m_out rows."""
    m, c = x.shape
    if m % m_out:
        raise ValueError(f"pool needs m_out | m, got {m} -> {m_out}")
    return x.reshape(m_out, m // m_out, c).mean(axis=1)


def act_relu_ref(x: jax.Array) -> jax.Array:
    return jax.nn.relu(x)


def bfp8_quant_ref(x: jax.Array, block: int = 32):
    """Block floating point: int8 mantissas + per-block exponent.
    x: (R, C) with C % block == 0.  Returns (mantissa i8, exponent i8)."""
    R, C = x.shape
    xb = x.astype(jnp.float32).reshape(R, C // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    exp = jnp.where(amax > 0,
                    jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-38))), 0.0)
    scale = jnp.exp2(exp - 6.0)
    man = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    return man.reshape(R, C).astype(jnp.int8), exp.astype(jnp.int8)


def bfp8_dequant_ref(man: jax.Array, exp: jax.Array, block: int = 32,
                     dtype=jnp.float32) -> jax.Array:
    R, C = man.shape
    scale = jnp.exp2(exp.astype(jnp.float32) - 6.0)
    out = man.astype(jnp.float32).reshape(R, C // block, block) \
        * scale[..., None]
    return out.reshape(R, C).astype(dtype)
