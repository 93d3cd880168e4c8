"""Fused blocked-scan precheck Pallas kernel (TPU).

The streaming blocked scan classifies every point of a block against the
current center buffer: nearest-center distance, nearest-center index, and
second-nearest distance (for the near-tie fallback margin). Historically
this was ``pdist``'s (B, T) distance matrix followed by host-side jnp glue
(min / argmin / one-hot-masked second min); this kernel fuses the whole
classification into one pass so the (B, T) matrix never round-trips
through HBM.

Same panel-matmul structure as ``pdist.py``: grid (gB, gd), LHS point
panels (bB, bd) and the full (padded) center buffer (T_pad, bd) staged
through VMEM, a (bB, T_pad) f32 squared-distance accumulator revisited
across the sequential d axis. On the last d step the kernel reduces the
accumulator in-register: masked sqrt, row min, first-index argmin (iota +
min over matching columns — ``jnp.argmin``'s tie rule), and the min with
the argmin column excluded. Output is a (B, 128) stats tile (cols 0..2 =
dmin, second, z; the 128-lane width is the natural TPU tile — slicing a
(B, 3) result would pad to the same tile anyway).

The center buffer is small (tau+1 rows), so one T_pad-wide block per step
is the right shape: the reduction needs the full row, and T_pad=128 keeps
VMEM per step at bB*bd + T_pad*bd + bB*T_pad floats (< 1 MiB at defaults).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# python literal (not a jnp scalar): pallas kernels must not close over
# traced array constants
_F32_MAX = float(jnp.finfo(jnp.float32).max)


def _precheck_kernel(x_ref, c_ref, m_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bB, bd)
    c = c_ref[...].astype(jnp.float32)  # (T_pad, bd)
    # full f32 passes: the scan's fallback margin assumes f32 cancellation
    # error, which a default-precision (bf16-pass) TPU matmul exceeds
    dot = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (bB, T_pad)
    xn = jnp.sum(x * x, axis=1, keepdims=True)  # (bB, 1)
    cn = jnp.sum(c * c, axis=1, keepdims=True).T  # (1, T_pad)
    acc_ref[...] += xn + cn - 2.0 * dot

    @pl.when(k == nk - 1)
    def _reduce():
        d2 = jnp.maximum(acc_ref[...], 0.0)  # (bB, T_pad)
        d = jnp.sqrt(d2)
        valid = m_ref[0:1, :] > 0.0  # (1, T_pad); padded cols invalid
        d = jnp.where(valid, d, _F32_MAX)
        tpad = d.shape[1]
        dmin = jnp.min(d, axis=1, keepdims=True)  # (bB, 1)
        cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        z = jnp.min(
            jnp.where(d == dmin, cols, jnp.int32(tpad)), axis=1,
            keepdims=True,
        )  # first col attaining the min == jnp.argmin's tie rule
        d_noz = jnp.where(cols == z, _F32_MAX, d)
        second = jnp.min(d_noz, axis=1, keepdims=True)
        z2 = jnp.min(
            jnp.where(d_noz == second, cols, jnp.int32(tpad)), axis=1,
            keepdims=True,
        )
        third = jnp.min(
            jnp.where(cols == z2, _F32_MAX, d_noz), axis=1, keepdims=True
        )
        oc = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
        o_ref[...] = (
            jnp.where(oc == 0, dmin, 0.0)
            + jnp.where(oc == 1, second, 0.0)
            + jnp.where(oc == 2, z.astype(jnp.float32), 0.0)
            + jnp.where(oc == 3, z2.astype(jnp.float32), 0.0)
            + jnp.where(oc == 4, third, 0.0)
        )


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_d", "interpret")
)
def center_precheck_stats(
    block: jnp.ndarray,  # (B, d) points
    centers: jnp.ndarray,  # (T, d) center buffer
    cvalid: jnp.ndarray,  # (T,) bool
    *,
    block_b: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(dmin, z, second, z2, third) nearest-center classification: the
    three smallest center distances per point and the indices of the two
    smallest, invalid centers masked to float32 max."""
    B, d = block.shape
    T, d2 = centers.shape
    assert d == d2, (block.shape, centers.shape)
    bB = min(block_b, max(8, B))
    bd = min(block_d, d)
    pB = -B % bB
    pT = -T % 128
    pd = -d % bd
    xp = jnp.pad(block, ((0, pB), (0, pd)))
    cp = jnp.pad(centers, ((0, pT), (0, pd)))
    tpad = cp.shape[0]
    # validity mask as an (8, T_pad) f32 plane: sublane-8 keeps the block
    # a whole min f32 tile; the kernel reads row 0
    mask = jnp.broadcast_to(
        jnp.pad(cvalid.astype(jnp.float32), (0, pT))[None, :], (8, tpad)
    )
    gB, gd = xp.shape[0] // bB, xp.shape[1] // bd
    out = pl.pallas_call(
        functools.partial(_precheck_kernel, nk=gd),
        grid=(gB, gd),
        in_specs=[
            pl.BlockSpec((bB, bd), lambda i, k: (i, k)),
            pl.BlockSpec((tpad, bd), lambda i, k: (0, k)),
            pl.BlockSpec((8, tpad), lambda i, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bB, 128), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bB, tpad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xp, cp, mask)
    stats = out[:B]
    return (
        stats[:, 0],
        stats[:, 2].astype(jnp.int32),
        stats[:, 1],
        stats[:, 3].astype(jnp.int32),
        stats[:, 4],
    )
