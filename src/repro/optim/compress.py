"""Gradient compression for cross-pod data parallelism.

The multi-pod mesh's ``pod`` axis crosses the slow inter-pod links, so the
per-step gradient all-reduce there is the collective-roofline term the
§Perf loop attacks for training cells.  int8 quantisation with **error
feedback** (the residual of each step's quantisation is added back into the
next step's gradient) keeps SGD/Adam convergence while cutting cross-pod
bytes 4x vs f32 / 2x vs bf16.

``compressed_psum`` runs the quantise -> psum -> dequantise sequence inside
``shard_map`` over the pod axis; per-pod backward passes stay GSPMD-sharded
over (data, model) via auto axes.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Row-wise (last-axis) int8 with fp32 scales."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax.astype(jnp.float32), 1e-20) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def ef_compress_tree(grads: Any, error: Any) -> tuple[Any, Any, Any]:
    """Error-feedback compression over a pytree.

    Returns (quantised payloads, scales, new error residuals).  The
    residual ``g + e - dq(q(g + e))`` is carried to the next step.
    """
    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        q, s = quantize_int8(corrected)
        back = dequantize_int8(q, s)
        return q, s, corrected - back

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(error)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]),
            tdef.unflatten([o[2] for o in out]))


def init_error_state(grads_like: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)


def compressed_psum(grads: Any, error: Any, axis_name: str = "pod"
                    ) -> tuple[Any, Any]:
    """Quantise + all-reduce over ``axis_name`` + dequantise, with error
    feedback.  Call INSIDE shard_map/pmap over the pod axis.

    Senders must agree on the scale before int payloads can be summed, so a
    cheap pmax over the (tiny) row scales runs first — the wire payload is
    then int8 mantissas + one shared fp32 scale per row: 4x fewer bytes on
    the slow inter-pod links than fp32 gradients.
    """
    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        amax = jnp.max(jnp.abs(corrected), axis=-1, keepdims=True)
        scale = jnp.maximum(amax.astype(jnp.float32), 1e-20) / 127.0
        scale = jax.lax.pmax(scale, axis_name)          # shared scale
        q = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(jnp.int8)
        new_e = corrected - q.astype(jnp.float32) * scale
        summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
        n = jax.lax.psum(1, axis_name)
        return (summed.astype(jnp.float32) * scale) / n, new_e

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(error)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


def make_pod_compressed_grad_fn(loss_fn, mesh):
    """Per-pod backward + int8-EF cross-pod reduction, via shard_map over
    the ``pod`` axis (data/model stay GSPMD-auto inside each pod).

    loss_fn(params, batch) -> scalar.  Returns
    fn(params, batch, error) -> (grads, loss, new_error)
    where ``batch`` is pod-sharded on its leading axis and ``params`` are
    replicated across pods.
    """
    def per_pod(params, batch, error):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, new_error = compressed_psum(grads, error, axis_name="pod")
        loss = jax.lax.pmean(loss, "pod")
        return grads, loss, new_error

    # manual over the pod axis only; data/model stay GSPMD-auto
    in_specs = (P(), P("pod"), P())
    out_specs = (P(), P(), P())
    return jax.shard_map(per_pod, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names={"pod"})
