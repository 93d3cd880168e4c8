"""Distributed (global) GMM farthest-first traversal — beyond-paper.

The paper's MR construction (§4.2) runs GMM independently per shard and
unions the per-shard coresets; correct by composability, but the union is a
tau_total = ell * tau_local clustering whose radius can be up to ~2x worse
than a GLOBAL tau-clustering of S (each shard re-discovers the same global
structure). This module runs ONE Gonzalez traversal over the sharded
dataset inside shard_map:

  per iteration: every shard folds the new center into its local min-dist
  vector (the same fused kernels/ops.gmm_update pass), then a global
  argmax is reached with one pmax + one masked pmax (O(1) scalars on the
  wire per iteration — the collective cost is tau * O(1), negligible next
  to the O(n*tau/ell) local distance work).

The result is byte-identical to single-machine GMM on the concatenated
data (tests/test_distributed_gmm.py), so Thm-5 coreset guarantees apply
with the GLOBAL tau rather than the per-shard sum — strictly smaller
coresets at equal radius (measured in benchmarks/fig3 commentary).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels import ops
from .coreset import Coreset, compress, default_capacity, extraction_mask
from .matroid import MatroidSpec


def _global_gmm_shard(pts, valid, tau: int, axes: Sequence[str]):
    """Runs inside shard_map. pts: (n_local, d). Returns
    (assign (n_local,), min_dist (n_local,), centers (tau, d), num, radius).
    """
    n_local = pts.shape[0]
    axes = tuple(axes)

    shard_idx = jnp.int32(0)
    for name in axes:
        shard_idx = shard_idx * jax.lax.axis_size(name) + jax.lax.axis_index(
            name
        )

    def pick_global(md):
        """Global argmax of masked min-dist: returns (value, center point).

        Two-round owner election so exact-value ties resolve to exactly ONE
        shard (elementwise pmax of two different points would mix
        coordinates)."""
        local_best = jnp.max(jnp.where(valid, md, -1.0))
        gbest = jax.lax.pmax(local_best, axes)
        contends = local_best >= gbest
        owner_tag = jnp.where(contends, -shard_idx.astype(jnp.float32),
                              -jnp.inf)
        best_owner = jax.lax.pmax(owner_tag, axes)
        is_owner = contends & (owner_tag >= best_owner)
        li = jnp.argmax(jnp.where(valid, md, -1.0))
        cand = jnp.where(is_owner, pts[li], -jnp.inf)
        center = jax.lax.pmax(cand, axes)
        return gbest, center

    # anchor: globally-first valid point (shard with lowest linear index
    # that has any valid point wins)
    has = jnp.any(valid)
    tag = jnp.where(has, -shard_idx.astype(jnp.float32), -jnp.inf)
    best_tag = jax.lax.pmax(tag, axes)
    anchor_owner = (tag >= best_tag) & has
    a_local = jnp.argmax(valid)
    anchor = jax.lax.pmax(
        jnp.where(anchor_owner, pts[a_local], -jnp.inf), axes
    )

    md0, _, _ = ops.gmm_update(
        pts, anchor, jnp.full((n_local,), jnp.inf, jnp.float32), valid
    )
    delta, z2 = pick_global(md0)

    centers0 = jnp.zeros((tau, pts.shape[1]), pts.dtype).at[0].set(anchor)
    assign0 = jnp.zeros((n_local,), jnp.int32)

    def body(t, state):
        centers, assign, md, nxt = state
        centers = centers.at[t].set(nxt)
        new_md, _, _ = ops.gmm_update(pts, nxt, md, valid)
        assign = jnp.where(new_md < md, t, assign)
        _, nxt2 = pick_global(new_md)
        return centers, assign, new_md, nxt2

    centers, assign, md, _ = jax.lax.fori_loop(
        1, tau, body, (centers0, assign0, md0, z2)
    )
    radius = jax.lax.pmax(jnp.max(jnp.where(valid, md, 0.0)), axes)
    return assign, md, centers, jnp.float32(delta), radius


def distributed_coreset(
    mesh: Mesh,
    points: jnp.ndarray,  # (n, d) global, n divisible by #shards
    cats: jnp.ndarray,
    valid: jnp.ndarray,
    spec: MatroidSpec,
    caps,
    k: int,
    tau: int,
    *,
    data_axes: Sequence[str] = ("data",),
):
    """Global-GMM coreset: one traversal over all shards, then the same
    EXTRACT masks as seq_coreset evaluated shard-locally, gathered.

    Returns (coreset replicated, radius, delta).
    """
    data_axes = tuple(data_axes)
    caps_arg = caps if caps is not None else jnp.zeros((1,), jnp.int32)
    cap = default_capacity(spec, k, tau)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(data_axes, None), P(data_axes, None), P(data_axes), P()),
        out_specs=(Coreset(P(), P(), P(), P()), P(), P()),
        check_vma=False,
    )
    def run(pts, cts, vld, caps_in):
        n_local = pts.shape[0]
        assign, _md, _centers, delta, radius = _global_gmm_shard(
            pts, vld, tau, data_axes
        )
        mask = extraction_mask(
            spec, assign, cts,
            caps_in if caps is not None else None, vld, k, tau,
        )
        idx = jnp.int32(0)
        for name in data_axes:
            idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
        cs = compress(pts, cts, mask, cap, base_index=idx * n_local)
        gathered = Coreset(
            *(jax.lax.all_gather(leaf, data_axes, tiled=True) for leaf in cs)
        )
        return gathered, radius, delta

    return run(points, cats, valid, caps_arg)
