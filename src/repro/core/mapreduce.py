"""MapReduce coreset construction (paper §4.2) as SPMD shard_map.

The paper's one-round MR scheme: partition S into ell shards, run SeqCoreset
on each shard (local delta_i, local GMM), union the local coresets. The
composability property (§3, [21]) makes the union a (1-eps)-coreset for S.

TPU mapping (DESIGN.md §3.3):
* a "reducer" is a mesh position along the data-parallel axes
  (``pod`` x ``data``); the map phase is the data pipeline's sharding;
* the union is one ``all_gather`` of the fixed-capacity coreset buffers;
* the optional second round (re-coreset of the union, making the final size
  independent of ell — paper §4.2 last paragraph) runs replicated on every
  device (identical inputs -> identical outputs, no extra communication).

Fault-tolerance note: the union of ANY subset of shard-coresets is a valid
coreset for the points those shards hold, so a straggler/failed shard
degrades coverage gracefully instead of poisoning the result (the driver can
mask out a shard by zeroing its ``valid`` lanes).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .coreset import Coreset, compress, default_capacity, extraction_mask, seq_coreset
from .matroid import MatroidSpec


def _flat_axis_index(axis_names: Sequence[str]) -> jnp.ndarray:
    """Linear shard index over (possibly multiple) mesh axes, C-order."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def local_coreset_and_gather(
    pts: jnp.ndarray,  # (n_local, d)
    cats: jnp.ndarray,  # (n_local, gamma)
    valid: jnp.ndarray,  # (n_local,)
    spec: MatroidSpec,
    caps: Optional[jnp.ndarray],
    k: int,
    tau_local: int,
    axis_names: Sequence[str],
    *,
    eps: float = 0.0,
    use_radius_target: bool = False,
    cap_local: Optional[int] = None,
) -> tuple[Coreset, jnp.ndarray]:
    """Runs inside shard_map: SeqCoreset on the local shard, then all_gather.

    Returns the union coreset (same on every shard) and the max overflow.
    """
    n_local = pts.shape[0]
    offset = _flat_axis_index(axis_names) * n_local
    cs, _res, ovf = seq_coreset(
        pts, cats, valid, spec, caps, k, tau_local,
        eps=eps, use_radius_target=use_radius_target,
        cap=cap_local, base_index=offset,
    )
    gathered = Coreset(
        *(
            jax.lax.all_gather(leaf, axis_names, tiled=True)
            for leaf in cs
        )
    )
    ovf = jax.lax.pmax(ovf, axis_names)
    return gathered, ovf


def mapreduce_coreset(
    mesh: Mesh,
    points: jnp.ndarray,  # (n, d) global, n divisible by #shards
    cats: jnp.ndarray,  # (n, gamma)
    valid: jnp.ndarray,  # (n,)
    spec: MatroidSpec,
    caps: Optional[jnp.ndarray],
    k: int,
    tau_local: int,
    *,
    data_axes: Sequence[str] = ("data",),
    eps: float = 0.0,
    use_radius_target: bool = False,
    round2_tau: Optional[int] = None,
) -> tuple[Coreset, jnp.ndarray]:
    """One (optionally two) MR round(s). Returns (coreset, overflow) with the
    coreset replicated across the mesh.

    round2_tau: if given, apply the sequential construction once more to the
    gathered union (paper: makes |T| independent of ell at the cost of an
    extra (1-eps) factor).
    """
    data_axes = tuple(data_axes)
    caps_arg = caps if caps is not None else jnp.zeros((1,), jnp.int32)

    in_spec = P(data_axes)
    pspec = P(data_axes, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(pspec, pspec, in_spec, P()),
        out_specs=(
            Coreset(P(), P(), P(), P()),
            P(),
        ),
        check_vma=False,
    )
    def run(pts, cts, vld, caps_in):
        cs, ovf = local_coreset_and_gather(
            pts, cts, vld, spec,
            caps_in if caps is not None else None,
            k, tau_local, data_axes,
            eps=eps, use_radius_target=use_radius_target,
        )
        if round2_tau is not None:
            cap2 = default_capacity(spec, k, round2_tau)
            cs2, _res2, ovf2 = seq_coreset(
                cs.points, cs.cats, cs.valid, spec,
                caps_in if caps is not None else None,
                k, round2_tau, cap=cap2,
                base_index=None,
            )
            # src_idx of round-2 points must chain through round-1's mapping
            safe = jnp.maximum(cs2.src_idx, 0)
            chained = jnp.where(cs2.valid, cs.src_idx[safe], -1)
            cs = cs2._replace(src_idx=chained)
            ovf = jnp.maximum(ovf, ovf2)
        return cs, ovf

    return run(points, cats, valid, caps_arg)
