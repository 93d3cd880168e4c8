"""Flash-attention forward Pallas kernel (TPU).

One (batch*head, q-block) cell keeps an (bq, hd) f32 accumulator plus
(bq,) running max/denominator in VMEM scratch while the sequential third
grid axis streams kv blocks through VMEM. This is the fused form of
models/attention.py's forward: on TPU it collapses the ~8 HLO elementwise
passes per block (mask/max/sub/exp/mul/add/...) into the matmul pipeline —
the dominant contributor to the memory roofline term of the dense
train/prefill cells (EXPERIMENTS §Roofline calibration note 4).

Layout: q/k/v pre-flattened to (BH, S, hd) with heads already expanded
(GQA rep applied by the caller, matching models/common.attn path).
VMEM per step: bq*hd + 2*bk*hd + bq*bk + scratch ≈ (512+2*1024)*128*4
+ 512*1024*4 ≈ 3.4 MiB at the default blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                      bq, bk, nk, causal, skv_real, scale):
    kj = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < skv_real
    if causal:
        mask = mask & (qpos >= kpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_sc[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (
            acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_block", "kv_block", "interpret"),
)
def flash_attention_fwd(
    q: jnp.ndarray,  # (BH, Sq, hd) heads pre-expanded/flattened
    k: jnp.ndarray,  # (BH, Skv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_block: int = 512,
    kv_block: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, sq0, hd = q.shape
    skv0 = k.shape[1]
    bq = min(q_block, sq0)
    bk = min(kv_block, skv0)
    pq = -sq0 % bq
    pk = -skv0 % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    sq, skv = sq0 + pq, skv0 + pk
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / (hd ** 0.5)

    out = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
            skv_real=skv0, scale=scale,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq0]


# ---------------------------------------------------------------------------
# backward kernels: dq (grid over q blocks) and dk/dv (grid over kv blocks),
# both recomputing probability blocks from (q, k, lse) — O(S*hd) residency.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                         dq_ref, dq_sc, *, bq, bk, nk, causal, skv_real,
                         scale):
    kj = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0].astype(jnp.float32)  # (bq,)
    dsum = dsum_ref[0][:, 0].astype(jnp.float32)  # (bq,)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < skv_real
    if causal:
        mask = mask & (qpos >= kpos)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)
    ds = p * (dp - dsum[:, None]) * scale
    dq_sc[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                          dk_ref, dv_ref, dk_sc, dv_sc, *, bq, bk, nq,
                          causal, skv_real, scale):
    qi = pl.program_id(2)
    kj = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0].astype(jnp.float32)
    dsum = dsum_ref[0][:, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < skv_real
    if causal:
        mask = mask & (qpos >= kpos)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])  # (bq, bk)
    dv_sc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bk, hd)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - dsum[:, None]) * scale
    dk_sc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bk, hd)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_block", "kv_block", "interpret"),
)
def flash_attention_bwd(
    q, k, v, o, lse, do,
    *,
    causal: bool = True,
    q_block: int = 512,
    kv_block: int = 1024,
    interpret: bool = False,
):
    """Returns (dq, dk, dv). q/k/v/o/do: (BH, S, hd); lse: (BH, Sq)."""
    bh, sq0, hd = q.shape
    skv0 = k.shape[1]
    bq = min(q_block, sq0)
    bk = min(kv_block, skv0)
    pq = -sq0 % bq
    pk = -skv0 % bk
    dsum = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (BH, Sq)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, pq), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pq)), constant_values=1.0)
        dsum = jnp.pad(dsum, ((0, 0), (0, pq)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    sq, skv = sq0 + pq, skv0 + pk
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / (hd ** 0.5)
    lse2 = lse[..., None]  # (BH, Sq, 1) — TPU-friendly 2D blocks
    dsum2 = dsum[..., None]

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
            skv_real=skv0, scale=scale,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse2, dsum2)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, bq=bq, bk=bk, nq=nq, causal=causal,
            skv_real=skv0, scale=scale,
        ),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, skv, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse2, dsum2)
    return dq[:, :sq0], dk[:, :skv0], dv[:, :skv0]
