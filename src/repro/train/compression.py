"""Int8 error-feedback gradient compression for cross-pod all-reduce.

At 2+ pods the inter-pod links are the scarcest bandwidth; compressing the
pod-level gradient reduction 4x (f32 -> int8 + per-tensor scale) with error
feedback (residual carried into the next step) preserves convergence
(Karimireddy et al., 2019). Wiring:

    comp, new_resid = compress_with_feedback(grad, resid)
    g_pod = psum(comp) over 'pod'  (int8 payload on the wire)
    grad  = decompress(g_pod)

Inside pjit the collective is implicit; ``make_pod_allreduce`` packages the
explicit shard_map version used by the tests and by launch/train.py when
``--compress-pod-grads`` is on.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def quantize(g: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(g)) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compress_with_feedback(
    grads: Any, residual: Any
) -> tuple[Any, Any, Any]:
    """Returns (quantized tree, scales tree, new residual tree)."""

    def one(g, r):
        gf = g.astype(jnp.float32) + r
        q, s = quantize(gf)
        deq = dequantize(q, s)
        return q, s, gf - deq

    out = jax.tree.map(one, grads, residual)
    treedef = jax.tree.structure(grads)
    flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
    qs = jax.tree.unflatten(treedef, [t[0] for t in flat])
    ss = jax.tree.unflatten(treedef, [t[1] for t in flat])
    rs = jax.tree.unflatten(treedef, [t[2] for t in flat])
    return qs, ss, rs


def init_residual(grads_like: Any) -> Any:
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_like
    )


def pod_allreduce_compressed(
    grads: Any, residual: Any, axis_name: str = "pod"
) -> tuple[Any, Any]:
    """Error-feedback int8 mean-all-reduce over ``axis_name`` (shard_map).

    All ranks agree on a shared per-tensor scale first (a scalar pmax — a
    negligible collective), so the int8 payloads are additive: psum in int32,
    then one dequantize. Residual = local quantization error, re-injected
    into the next step's gradient (error feedback)."""

    def one(g, r):
        gf = g.astype(jnp.float32) + r
        amax = jax.lax.pmax(jnp.max(jnp.abs(gf)), axis_name)
        scale = jnp.maximum(amax / 127.0, 1e-30)
        q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
        deq_local = q.astype(jnp.float32) * scale
        new_r = gf - deq_local
        n = jax.lax.axis_size(axis_name)
        total = jax.lax.psum(q.astype(jnp.int32), axis_name)
        return total.astype(jnp.float32) * scale / n, new_r

    out = jax.tree.map(one, grads, residual)
    treedef = jax.tree.structure(grads)
    flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
    red = jax.tree.unflatten(treedef, [t[0] for t in flat])
    new_resid = jax.tree.unflatten(treedef, [t[1] for t in flat])
    return red, new_resid
