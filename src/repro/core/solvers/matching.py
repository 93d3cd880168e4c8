"""Jit-side bipartite matching for transversal matroids.

Two layers of machinery, both static-shape and mask-based so they can run
inside jit/vmap:

* ``greedy_matching_slots`` — the greedy matching witness used by the
  streaming shrink step (Alg. 2): sound for proving "an independent size-k
  subset exists", may overcount nothing but can under-match. Lifted here
  from ``core.streaming._shrink`` so the scan and the solvers share one
  implementation.

* Exact augmenting-path primitives (Kuhn's algorithm over masks) used by
  the batched final-stage solvers: a transversal feasibility check is
  "does an augmenting path from candidate v exist given a complete
  matching of the current selection" — exactly the host oracle's
  ``can_extend`` truth value, independent of *which* complete matching is
  maintained (standard alternating-path argument), so the jit solver makes
  bit-identical accept/reject decisions to the host local search.

Matching representation for the exact primitives: ``ms_pt: int32[h]`` maps
category -> matched point id (local row of the coreset matrix), -1 if the
category is free. Category incidence is a dense one-hot ``oh: bool[m, h]``
(points on the left, categories on the right).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def cats_onehot(cats: np.ndarray, num_categories: int) -> np.ndarray:
    """(m, gamma) -1-padded label matrix -> bool[m, h] incidence."""
    cats = np.asarray(cats, np.int64)
    if cats.ndim == 1:
        cats = cats[:, None]
    m = cats.shape[0]
    oh = np.zeros((m, num_categories), bool)
    rows, cols = np.nonzero(cats >= 0)
    oh[rows, cats[rows, cols]] = True
    return oh


# --------------------------------------------------------------------------
# Greedy matching witness (shared with core.streaming._shrink)
# --------------------------------------------------------------------------


def greedy_matching_slots(
    cats: jnp.ndarray,  # (SLOT, gamma) int32, -1 padded
    valid: jnp.ndarray,  # (SLOT,) bool
    num_categories: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """First-free-category greedy matching over slot order.

    Returns (used: bool[h] categories consumed, matched: bool[SLOT] slots
    that found a category). Exactly what the sequential loop the streaming
    shrink step has always run gives — each valid slot, in slot order,
    takes the first of its categories no earlier slot took (the loop is
    kept in tests/test_matroid.py as the reference;
    tests/test_blocked_ingest.py pins the scan output across refactors).

    Computed as the fixed point of one vectorized round: every valid slot
    picks its first category that no earlier slot holds in the previous
    round's picks. A slot's greedy pick depends only on earlier slots', so
    after round r the first r valid slots are final, and the first round
    that changes nothing has reached the greedy result. The trip count is
    the longest chain of displaced picks — a handful — instead of the
    number of valid slots (about k per transversal center), which on a TPU,
    where each sequential trip pays a fixed cost, dominates the scan step.
    """
    slot_n, _gamma = cats.shape
    pos = jnp.arange(slot_n, dtype=jnp.int32)
    cat_ids = jnp.arange(num_categories, dtype=jnp.int32)
    real = (cats >= 0) & valid[:, None]
    cc = jnp.maximum(cats, 0)

    def round_(pick):
        # first[c]: earliest slot holding category c (slot_n when none)
        first = jnp.min(
            jnp.where(pick[None, :] == cat_ids[:, None], pos[None, :], slot_n),
            axis=1,
        )
        free = real & (first[cc] >= pos[:, None])
        j = jnp.argmax(free, axis=1)  # first free category slot
        got = jnp.take_along_axis(cats, j[:, None], axis=1)[:, 0]
        return jnp.where(jnp.any(free, axis=1), got, -1)

    none = jnp.full((slot_n,), -1, jnp.int32)
    pick, _ = jax.lax.while_loop(
        lambda c: jnp.any(c[0] != c[1]),
        lambda c: (round_(c[0]), c[0]),
        (round_(none), none),
    )
    matched = pick >= 0
    used = jnp.any(pick[None, :] == cat_ids[:, None], axis=1)
    return used, matched


# --------------------------------------------------------------------------
# Exact augmenting-path primitives (Kuhn over masks)
# --------------------------------------------------------------------------


def reach_matrix(oh: jnp.ndarray, ms_pt: jnp.ndarray) -> jnp.ndarray:
    """bool[h, h] one-step alternating reachability between categories.

    M[c, c'] is True iff category c is matched (to point p = ms_pt[c]) and
    p also holds category c' — i.e. an alternating path entering c can
    continue to c' through p.
    """
    p = jnp.maximum(ms_pt, 0)
    return oh[p] & (ms_pt >= 0)[:, None]


def feasible_all(
    oh: jnp.ndarray,  # (m, h) bool point-category incidence
    ms_pt: jnp.ndarray,  # (h,) int32 matching (point id or -1)
    iters: int,  # >= current matching size (kmax is always safe)
) -> jnp.ndarray:
    """bool[m]: for every point v, does an augmenting path from v exist?

    Equivalently: is (current selection) + {v} independent in the
    transversal matroid — the host ``can_extend`` answer for all m
    candidates at once. Fixpoint reachability over the h-category graph;
    an alternating path traverses at most one matched point per step, so
    ``iters`` >= matching size reaches the fixpoint.
    """
    M = reach_matrix(oh, ms_pt).astype(jnp.float32)
    free = (ms_pt < 0)[None, :]

    def step(_, reach):
        return reach | ((reach.astype(jnp.float32) @ M) > 0)

    reach = jax.lax.fori_loop(0, iters, step, oh)
    return jnp.any(reach & free, axis=1)


def swap_feasible(
    oh: jnp.ndarray,  # (m, h) bool
    ms_pt: jnp.ndarray,  # (h,) int32
    sel: jnp.ndarray,  # (kmax,) int32 selected point ids (-1 padded)
    v,  # candidate point id
) -> jnp.ndarray:
    """bool[kmax]: for every selected slot j, is X - sel[j] + v independent?

    Variant j frees sel[j]'s matched category, then asks for an augmenting
    path from v. Rows for invalid slots (sel[j] < 0) are garbage; callers
    mask them with ``slots < nsel``.
    """
    kmax = sel.shape[0]
    h = ms_pt.shape[0]
    u = jnp.maximum(sel, 0)
    ms_var = jnp.where(ms_pt[None, :] == u[:, None], -1, ms_pt[None, :])
    Ms = jax.vmap(reach_matrix, in_axes=(None, 0))(oh, ms_var)
    Ms = Ms.astype(jnp.float32)  # (kmax, h, h)
    free = ms_var < 0  # (kmax, h)
    reach0 = jnp.broadcast_to(oh[v], (kmax, h))

    def step(_, reach):
        nxt = jnp.einsum("jc,jcd->jd", reach.astype(jnp.float32), Ms) > 0
        return reach | nxt

    reach = jax.lax.fori_loop(0, kmax, step, reach0)
    return jnp.any(reach & free, axis=1)


def augment(
    oh: jnp.ndarray,  # (m, h) bool
    ms_pt: jnp.ndarray,  # (h,) int32
    v,  # point id to insert
    iters: int,  # >= matching size (kmax is always safe)
) -> jnp.ndarray:
    """Insert point v into the matching via one augmenting path (BFS +
    flip). Returns the updated ``ms_pt``; a no-op when no path exists (the
    callers always pre-check feasibility, this just keeps the masked
    branch safe)."""
    h = ms_pt.shape[0]
    ohv = oh[v]
    M = reach_matrix(oh, ms_pt)
    # from_cat[c]: BFS parent category of c (-1: reached directly from v,
    # -2: unvisited)
    from_cat0 = jnp.where(ohv, jnp.int32(-1), jnp.int32(-2))

    def bfs(_, carry):
        from_cat, frontier = carry
        cand = frontier[:, None] & M  # (h, h): edge c -> c'
        new = jnp.any(cand, axis=0) & (from_cat == -2)
        parent = jnp.argmax(cand, axis=0).astype(jnp.int32)
        return jnp.where(new, parent, from_cat), new

    from_cat, _ = jax.lax.fori_loop(0, iters, bfs, (from_cat0, ohv))
    endpoint = (from_cat > -2) & (ms_pt < 0)  # visited AND free
    ok = jnp.any(endpoint)
    c_end = jnp.argmax(endpoint).astype(jnp.int32)

    # Walk the path back from the free endpoint, shifting each matched
    # point one category forward; the category adjacent to v gets v.
    def cond_fn(carry):
        _ms, _c, done, i = carry
        return ~done & (i <= h)

    def body_fn(carry):
        ms, c, _done, i = carry
        cp = from_cat[c]
        moved = jnp.where(cp < 0, jnp.int32(v), ms[jnp.maximum(cp, 0)])
        return ms.at[c].set(moved), jnp.maximum(cp, 0), cp < 0, i + 1

    ms2, _, _, _ = jax.lax.while_loop(
        cond_fn, body_fn, (ms_pt, c_end, ~ok, jnp.int32(0))
    )
    return ms2
