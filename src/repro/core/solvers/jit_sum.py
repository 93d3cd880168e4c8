"""Vectorized batched sum-variant engine (uniform/partition/transversal).

``solve_sum_batch`` answers a batch of heterogeneous sum-diversity queries
(per-query k, category caps, candidate filters) against ONE cached coreset
distance matrix: a vmapped greedy seeding + masked first-improvement local
search, mirroring ``solvers.local_search.local_search_sum`` step for step
(same greedy gains, same (v, u) scan order, same incremental swap value, X
kept in insertion order) so the fast path lands on the same local optimum
as the host solver on the same matrix.

Matroid feasibility inside the greedy/swap loops comes in two flavours,
chosen statically per matroid kind:

* uniform/partition — the O(1) ``counts < caps`` check (uniform is a
  single pseudo-category nobody caps);
* transversal — the masked augmenting-path primitives of
  ``solvers.matching``: "can candidate v extend (or swap into) the current
  selection" is answered exactly, by the same alternating-path truth the
  host oracle computes, so accept/reject decisions are identical to
  ``local_search_sum`` under a ``TransversalMatroid``.

Everything is masked to static shapes: queries are padded to the batch's
``kmax`` (bucketed to the next power of two so novel max-k values don't
recompile) and the batch to a power-of-two length; infeasible queries
simply stop early (nsel < k) like the host solver does.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ..diversity import Variant
from .base import (
    EngineSolution,
    SolveContext,
    SolveSpec,
    SolverEngine,
    selection_value,
)
from .matching import augment, cats_onehot, feasible_all, swap_feasible


def _dot(a, b):
    """Full-f32 matmul for the gain and swap-value decisions: the host
    solver computes them in f32/f64, and a default-precision TPU matmul
    takes bf16 passes that reorder near-tied candidates."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def bucket_pow2(n: int) -> int:
    """Next power of two >= n (>= 1). Shape-bucketing for the jit cache:
    a batch of 5 queries with max k 6 compiles the (8, 8) kernel, and any
    later batch with B <= 8, k <= 8 reuses it."""
    return 1 << max(0, int(n - 1).bit_length())


def jit_cell_eligible(
    engine: SolverEngine, ctx: SolveContext, spec: SolveSpec
) -> bool:
    """Data-dependent eligibility shared by the jit batch engines."""
    if not engine.supports(spec.variant, ctx.spec.kind):
        return False
    if not spec.ascending_candidates(ctx.size):
        return False  # custom candidate order is host-solver territory
    if ctx.spec.kind != "uniform" and ctx.cats is None:
        return False  # jit path needs the category matrix
    if ctx.spec.kind == "partition":
        # a partition matroid is single-label by definition; rows with a
        # second real label must go to the host oracle, which raises the
        # descriptive error (never truncate silently)
        if ctx.partition_multilabel():
            return False
        if ctx.caps is None and spec.caps is None:
            return False
    return True


def pad_query_arrays(
    ctx: SolveContext, specs: Sequence[SolveSpec], Bb: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(allow (Bb, m), ks (Bb,), gammas (Bb,)) with power-of-two padding
    rows that solve k=0 no-op queries."""
    m = ctx.size
    allow_b = np.zeros((Bb, m), bool)
    ks = np.zeros((Bb,), np.int32)
    gammas = np.zeros((Bb,), np.float32)
    for i, s in enumerate(specs):
        allow_b[i] = s.allow_mask(m)
        ks[i] = s.k
        gammas[i] = s.gamma
    return allow_b, ks, gammas


def partition_arrays(
    ctx: SolveContext, specs: Sequence[SolveSpec], Bb: int
) -> tuple[np.ndarray, np.ndarray]:
    """(cats1 (m,), caps_b (Bb, h)) for the counts<caps feasibility path;
    uniform matroids become one pseudo-category nobody caps."""
    m = ctx.size
    if ctx.spec.kind == "partition":
        cats1 = np.asarray(ctx.cats[:, 0], np.int32)
        h = ctx.spec.num_categories
        default_caps = ctx.caps
    else:  # uniform
        cats1 = np.zeros((m,), np.int32)
        h = 1
        default_caps = None
    caps_b = np.full((Bb, h), m + 1, np.int32)  # padding rows: uncapped
    for i, s in enumerate(specs):
        if s.caps is not None:
            caps_b[i] = np.asarray(s.caps, np.int32)
        elif default_caps is not None:
            caps_b[i] = default_caps
    return cats1, caps_b


# --------------------------------------------------------------------------
# uniform / partition: counts-based feasibility (historical fast path)
# --------------------------------------------------------------------------


def _greedy_seed(D, cats, caps, allow, k, kmax):
    """Mirror of local_search.greedy_init: max marginal-gain candidate per
    step (first index wins ties), partition feasibility via counts<caps."""
    m = D.shape[0]
    h = caps.shape[0]
    rowsum_all = jnp.sum(D, axis=1)  # gain of the very first pick

    def body(i, carry):
        sel, selmask, counts, nsel = carry
        can = allow & ~selmask & (counts[cats] < caps[cats])
        gains = jnp.where(
            nsel == 0, rowsum_all, _dot(D, selmask.astype(jnp.float32))
        )
        v = jnp.argmax(jnp.where(can, gains, -jnp.inf))
        take = (i < k) & jnp.any(can)

        def add(c):
            sel, selmask, counts, nsel = c
            return (
                sel.at[nsel].set(v),
                selmask.at[v].set(True),
                counts.at[cats[v]].add(1),
                nsel + 1,
            )

        return jax.lax.cond(take, add, lambda c: c, carry)

    init = (
        jnp.full((kmax,), -1, jnp.int32),
        jnp.zeros((m,), bool),
        jnp.zeros((h,), jnp.int32),
        jnp.int32(0),
    )
    return jax.lax.fori_loop(0, kmax, body, init)


def _solve_sum_one(D, cats, caps, allow, k, gamma, *, kmax, max_sweeps):
    """Single-query greedy + first-improvement local search over cached D."""
    m = D.shape[0]
    sel, selmask, counts, nsel = _greedy_seed(D, cats, caps, allow, k, kmax)
    selm_f = selmask.astype(jnp.float32)
    div0 = 0.5 * _dot(selm_f, _dot(D, selm_f))
    slots = jnp.arange(kmax, dtype=jnp.int32)

    def v_body(v, st):
        sel, selmask, counts, rowX, div, improved = st
        u = jnp.maximum(sel, 0)  # (kmax,) slot -> local id (garbage past k)
        # div(X - u + v) = div - row[u] + dv - d(u, v)   (host's identity)
        new_div = div - rowX[u] + rowX[v] - D[u, v]
        cat_v = cats[v]
        ok_cap = counts[cat_v] - (cats[u] == cat_v) + 1 <= caps[cat_v]
        improving = (
            (slots < nsel)
            & (new_div > div * (1.0 + gamma))
            & (new_div > div)
            & ok_cap
        )
        any_imp = allow[v] & ~selmask[v] & jnp.any(improving)
        ui = jnp.argmax(improving)  # first improving u in X order

        def do_swap(st):
            sel, selmask, counts, rowX, div, improved = st
            uold = sel[ui]
            # host order: X = [w for w in X if w != u] + [v]
            src = jnp.where(slots >= ui, jnp.minimum(slots + 1, kmax - 1), slots)
            sel2 = sel[src].at[nsel - 1].set(v)
            selmask2 = selmask.at[uold].set(False).at[v].set(True)
            counts2 = counts.at[cats[uold]].add(-1).at[cat_v].add(1)
            rowX2 = _dot(D, selmask2.astype(jnp.float32))
            return sel2, selmask2, counts2, rowX2, new_div[ui], True

        return jax.lax.cond(any_imp, do_swap, lambda s: s, st)

    def sweep_cond(carry):
        st, sweeps = carry
        return st[-1] & (sweeps < max_sweeps)

    def sweep_body(carry):
        st, sweeps = carry
        st = (*st[:-1], False)
        st = jax.lax.fori_loop(0, m, v_body, st)
        return st, sweeps + 1

    rowX0 = _dot(D, selm_f)
    ls0 = ((sel, selmask, counts, rowX0, div0, nsel == k), jnp.int32(0))
    (sel, selmask, counts, _rowX, div, _imp), _ = jax.lax.while_loop(
        sweep_cond, sweep_body, ls0
    )
    return sel, nsel, div


@functools.partial(jax.jit, static_argnames=("kmax", "max_sweeps"))
def solve_sum_batch(
    D: jnp.ndarray,  # (m, m) cached coreset distances
    cats: jnp.ndarray,  # (m,) int32 single-label categories (zeros: uniform)
    caps: jnp.ndarray,  # (B, h) per-query caps
    allow: jnp.ndarray,  # (B, m) per-query candidate masks
    ks: jnp.ndarray,  # (B,)
    gammas: jnp.ndarray,  # (B,)
    *,
    kmax: int,
    max_sweeps: int = 64,
):
    """Batch of sum-DMMC queries on one matrix (uniform/partition).
    Returns (sel (B, kmax) local ids -1-padded, nsel (B,), div (B,))."""
    f = functools.partial(_solve_sum_one, kmax=kmax, max_sweeps=max_sweeps)
    with jax.named_scope("solver/jit_sum"):
        return jax.vmap(f, in_axes=(None, None, 0, 0, 0, 0))(
            D, cats, caps, allow, ks, gammas
        )


# --------------------------------------------------------------------------
# transversal: augmenting-path feasibility
# --------------------------------------------------------------------------


def _greedy_seed_tv(D, oh, allow, k, kmax):
    """Greedy seeding under a transversal matroid: same gains/tie-breaks
    as ``_greedy_seed``, feasibility = augmenting path exists (exact)."""
    m = D.shape[0]
    h = oh.shape[1]
    rowsum_all = jnp.sum(D, axis=1)

    def body(i, carry):
        sel, selmask, ms_pt, nsel = carry
        can = allow & ~selmask & feasible_all(oh, ms_pt, kmax)
        gains = jnp.where(
            nsel == 0, rowsum_all, _dot(D, selmask.astype(jnp.float32))
        )
        v = jnp.argmax(jnp.where(can, gains, -jnp.inf))
        take = (i < k) & jnp.any(can)

        def add(c):
            sel, selmask, ms_pt, nsel = c
            return (
                sel.at[nsel].set(v),
                selmask.at[v].set(True),
                augment(oh, ms_pt, v, kmax),
                nsel + 1,
            )

        return jax.lax.cond(take, add, lambda c: c, carry)

    init = (
        jnp.full((kmax,), -1, jnp.int32),
        jnp.zeros((m,), bool),
        jnp.full((h,), -1, jnp.int32),
        jnp.int32(0),
    )
    return jax.lax.fori_loop(0, kmax, body, init)


def _solve_sum_one_tv(D, oh, allow, k, gamma, *, kmax, max_sweeps):
    """Single transversal sum query: greedy + first-improvement local
    search, swap feasibility via masked augmenting paths. Mirrors
    ``local_search_sum`` under a ``TransversalMatroid`` decision for
    decision (feasibility truth is matching-independent)."""
    m = D.shape[0]
    sel, selmask, ms_pt, nsel = _greedy_seed_tv(D, oh, allow, k, kmax)
    selm_f = selmask.astype(jnp.float32)
    div0 = 0.5 * _dot(selm_f, _dot(D, selm_f))
    slots = jnp.arange(kmax, dtype=jnp.int32)

    def v_body(v, st):
        sel, selmask, ms_pt, rowX, div, improved = st
        u = jnp.maximum(sel, 0)
        new_div = div - rowX[u] + rowX[v] - D[u, v]
        ok_swap = swap_feasible(oh, ms_pt, sel, v)  # (kmax,) exact
        improving = (
            (slots < nsel)
            & (new_div > div * (1.0 + gamma))
            & (new_div > div)
            & ok_swap
        )
        any_imp = allow[v] & ~selmask[v] & jnp.any(improving)
        ui = jnp.argmax(improving)

        def do_swap(st):
            sel, selmask, ms_pt, rowX, div, improved = st
            uold = sel[ui]
            src = jnp.where(slots >= ui, jnp.minimum(slots + 1, kmax - 1), slots)
            sel2 = sel[src].at[nsel - 1].set(v)
            selmask2 = selmask.at[uold].set(False).at[v].set(True)
            # rebuild the matching: free u's category, re-insert v
            ms2 = jnp.where(ms_pt == uold, jnp.int32(-1), ms_pt)
            ms2 = augment(oh, ms2, v, kmax)
            rowX2 = _dot(D, selmask2.astype(jnp.float32))
            return sel2, selmask2, ms2, rowX2, new_div[ui], True

        return jax.lax.cond(any_imp, do_swap, lambda s: s, st)

    def sweep_cond(carry):
        st, sweeps = carry
        return st[-1] & (sweeps < max_sweeps)

    def sweep_body(carry):
        st, sweeps = carry
        st = (*st[:-1], False)
        st = jax.lax.fori_loop(0, m, v_body, st)
        return st, sweeps + 1

    rowX0 = _dot(D, selm_f)
    ls0 = ((sel, selmask, ms_pt, rowX0, div0, nsel == k), jnp.int32(0))
    (sel, _selmask, _ms, _rowX, div, _imp), _ = jax.lax.while_loop(
        sweep_cond, sweep_body, ls0
    )
    return sel, nsel, div


@functools.partial(jax.jit, static_argnames=("kmax", "max_sweeps"))
def solve_sum_batch_transversal(
    D: jnp.ndarray,  # (m, m)
    oh: jnp.ndarray,  # (m, h) bool point-category incidence
    allow: jnp.ndarray,  # (B, m)
    ks: jnp.ndarray,  # (B,)
    gammas: jnp.ndarray,  # (B,)
    *,
    kmax: int,
    max_sweeps: int = 64,
):
    """Batch of sum-DMMC queries under ONE transversal matroid.
    Returns (sel (B, kmax) -1-padded, nsel (B,), div (B,))."""
    f = functools.partial(_solve_sum_one_tv, kmax=kmax, max_sweeps=max_sweeps)
    with jax.named_scope("solver/jit_sum_tv"):
        return jax.vmap(f, in_axes=(None, None, 0, 0, 0))(
            D, oh, allow, ks, gammas
        )


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------


class JitSumBatchEngine(SolverEngine):
    """Registry face of the two batched jit solvers above."""

    name = "jit_sum"
    priority = 10
    exact_parity = True  # mirrors host local search step for step

    def supports(self, variant: Variant, matroid_kind: str) -> bool:
        return variant == "sum" and matroid_kind in (
            "uniform", "partition", "transversal"
        )

    def eligible(self, ctx: SolveContext, spec: SolveSpec) -> bool:
        return jit_cell_eligible(self, ctx, spec)

    def stack_eligible(self, ctx: SolveContext, spec: SolveSpec) -> bool:
        # local import: stacked.py reuses this module's row solver
        from .stacked import counts_stack_eligible

        return counts_stack_eligible(self, ctx, spec)

    def solve_batch_stacked(self, lanes) -> "list[list[EngineSolution]]":
        from .stacked import solve_stacked

        return solve_stacked(lanes)

    def solve_batch(
        self, ctx: SolveContext, specs: Sequence[SolveSpec]
    ) -> list[EngineSolution]:
        Bb = bucket_pow2(len(specs))
        kmax = bucket_pow2(max((s.k for s in specs), default=1))
        allow_b, ks, gammas = pad_query_arrays(ctx, specs, Bb)

        if ctx.spec.kind == "transversal":
            oh = cats_onehot(ctx.cats, ctx.spec.num_categories)
            with obs.compile_region(
                f"solve[jit_sum_tv B={Bb} kmax={kmax} m={ctx.size}]"
            ):
                sel, nsel, _div = solve_sum_batch_transversal(
                    jnp.asarray(ctx.D),
                    jnp.asarray(oh),
                    jnp.asarray(allow_b),
                    jnp.asarray(ks),
                    jnp.asarray(gammas),
                    kmax=kmax,
                )
        else:
            cats1, caps_b = partition_arrays(ctx, specs, Bb)
            with obs.compile_region(
                f"solve[jit_sum B={Bb} kmax={kmax} m={ctx.size}]"
            ):
                sel, nsel, _div = solve_sum_batch(
                    jnp.asarray(ctx.D),
                    jnp.asarray(cats1),
                    jnp.asarray(caps_b),
                    jnp.asarray(allow_b),
                    jnp.asarray(ks),
                    jnp.asarray(gammas),
                    kmax=kmax,
                )

        sel, nsel = np.asarray(sel), np.asarray(nsel)
        out = []
        for i, s in enumerate(specs):
            loc = sel[i, : nsel[i]].astype(np.int64)
            # the jit solver accumulates its objective in f32; the indices
            # are what it decided on — report the canonical f64 value
            out.append(
                EngineSolution(
                    local_indices=loc,
                    value=selection_value(ctx.D, loc, s.variant),
                    engine=self.name,
                )
            )
        return out
