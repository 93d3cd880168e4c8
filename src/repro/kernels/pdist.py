"""Blocked pairwise squared-distance Pallas kernel (TPU).

Computes D2[i, j] = ||x_i||^2 + ||y_j||^2 - 2 x_i . y_j with a 3-D grid
(gn, gm, gd): LHS/RHS panels of shape (bn, bd) / (bm, bd) are staged through
VMEM and a (bn, bm) f32 accumulator tile is revisited across the d-grid axis
(dimension_semantics: the d axis is 'arbitrary', i.e. sequential, so the
accumulation is well-defined).

Design notes (TPU):
* the dominant op is the (bn, bd) @ (bd, bm) panel matmul -> MXU;
  block sizes default to 256/256/512, all multiples of the 128 MXU tile;
* VMEM per step = bn*bd + bm*bd + bn*bm floats ~= (256*512*2 + 256*256)*4B
  ~= 1.3 MiB, comfortably under the ~16 MiB/core budget, leaving room for
  double-buffered prefetch of the next panels;
* norms are accumulated per d-tile alongside the dot product so the kernel
  makes exactly one pass over the operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pdist_kernel(x_ref, y_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    y = y_ref[...].astype(jnp.float32)  # (bm, bd)
    dot = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # f32 distances, not bf16 passes
    )  # (bn, bm)
    xn = jnp.sum(x * x, axis=1, keepdims=True)  # (bn, 1)
    yn = jnp.sum(y * y, axis=1, keepdims=True).T  # (1, bm)
    o_ref[...] += xn + yn - 2.0 * dot


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "block_d", "interpret")
)
def pairwise_sqdist(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    block_n: int = 256,
    block_m: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """(n, d), (m, d) -> (n, m) squared distances. Pads to block multiples."""
    n, d = x.shape
    m, d2 = y.shape
    assert d == d2, (x.shape, y.shape)
    bn = min(block_n, max(8, n))
    bm = min(block_m, max(8, m))
    bd = min(block_d, d)
    pn = -n % bn
    pm = -m % bm
    pd = -d % bd
    xp = jnp.pad(x, ((0, pn), (0, pd)))
    yp = jnp.pad(y, ((0, pm), (0, pd)))
    gn, gm, gd = xp.shape[0] // bn, yp.shape[0] // bm, xp.shape[1] // bd
    out = pl.pallas_call(
        _pdist_kernel,
        grid=(gn, gm, gd),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], yp.shape[0]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xp, yp)
    return jnp.maximum(out[:n, :m], 0.0)
