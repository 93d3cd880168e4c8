"""Vectorized batched sum-variant engine (uniform/partition/transversal).

``solve_sum_batch`` answers a batch of heterogeneous sum-diversity queries
(per-query k, category caps, candidate filters) against ONE cached coreset
distance matrix: a vmapped greedy seeding + masked first-improvement local
search, mirroring ``solvers.local_search.local_search_sum`` step for step
(same greedy gains, same (v, u) scan order, same incremental swap value, X
kept in insertion order) so the fast path lands on the same local optimum
as the host solver on the same matrix.

The local search's state changes only on a swap, so one sweep tests a
block of T candidates per trip of a ``while_loop`` (``_local_search``,
shared by both feasibility families): every (u, v) swap value of the block
with the host's arithmetic, then the first v with an improving, feasible
u, and the first such u in X order. On a swap the next trip starts at
v + 1, else after the block: the host's decisions in ~m/T + swaps trips a
sweep instead of m. T is a power of two from the static shapes
(``_block_size``). The swap itself (new selection, counts or matching, and
the full ``D @ X`` row-sum recompute) runs under ``streaming._cond_once``,
a single-trip ``while_loop``: under vmap a ``lax.cond`` lowers to a select
that would run the swap, and read all of D, on every trip. With the loop
it runs only on the trips where some row of the batch swaps.
Each launch also returns the number of swaps its rows made (summed) and the
trips of its sweep and block loops (the most any row made), for the
``solve.run`` span, which also records the candidate visits the sweeps
cover (every row of a vmapped sweep loop covers all m candidates for as
many sweeps as the loop made) and the matroid kind.

Matroid feasibility inside the greedy/swap loops comes in two flavours,
chosen statically per matroid kind:

* uniform/partition — the O(1) ``counts < caps`` check (uniform is a
  single pseudo-category nobody caps);
* transversal — the masked augmenting-path primitives of
  ``solvers.matching``: "can candidate v extend (or swap into) the current
  selection" is answered exactly, by the same alternating-path truth the
  host oracle computes, so accept/reject decisions are identical to
  ``local_search_sum`` under a ``TransversalMatroid``. The swap test of
  every candidate reads one ``swap_table`` per selection, rebuilt after
  each swap, instead of walking paths per candidate.

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
from ..streaming import _cond_once
from .base import (
    EngineSolution,
    SolveContext,
    SolveSpec,
    SolverEngine,
    selection_value,
)
from .matching import augment, cats_onehot, feasible_all, swap_table


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


def to_device(*host) -> list:
    """``jnp.asarray`` of each argument of a jit solver launch, inside the
    ``solve.h2d`` span whose ``bytes`` arg sums the host arrays copied.
    Nothing waits here: a copy still in flight finishes in ``solve.run``."""
    nbytes = sum(a.nbytes for a in host if isinstance(a, np.ndarray))
    with obs.span("solve.h2d", cat="query", bytes=nbytes):
        return [jnp.asarray(a) for a in host]


def vmap_rows(row_solver, D, matroid, *rows):
    """``row_solver`` vmapped over the batch's rows, with D and the shared
    matroid array unmapped. Returns (sel, nsel, div) per row, then three
    int32 scalars: ``swaps``, the swaps all rows made, ``sweeps``, the
    trips of the batched sweep loop, and ``trips``, those of its block
    loop (each the most any row made)."""
    in_axes = (None, None) + (0,) * len(rows)
    sel, nsel, div, swaps, sweeps, trips = jax.vmap(
        row_solver, in_axes=in_axes)(D, matroid, *rows)
    return sel, nsel, div, jnp.sum(swaps), jnp.max(sweeps), jnp.max(trips)


# --------------------------------------------------------------------------
# the local search, shared by both feasibility families
# --------------------------------------------------------------------------


def _block_size(m: int, kmax: int) -> int:
    """Candidates one trip of the local search's block loop tests: the
    largest power of two T <= 256 with T * kmax <= 32768, and no more
    than m. A trip costs a fixed loop overhead plus work in T * kmax
    (the (kmax, T) swap values and tests), and a swap at lane c wastes
    the lanes after it, since the next trip starts at the candidate
    after it. On a TPU v5e the fixed cost rules: at kmax 128 a trip of
    256 candidates cost about what one of 64 did, so T is as large as
    keeps the (kmax, T) values at 128 KiB."""
    t = 1
    while 2 * t <= 256 and 2 * t * kmax <= 32768:
        t *= 2
    return min(t, m)


def _local_search(D, allow, k, gamma, sel, selmask, nsel, mat, swap_ok,
                  swap_mat, *, kmax, max_sweeps):
    """First-improvement local search from a greedy seed, one query.

    Each sweep scans the candidates in blocks: a trip takes the T
    candidates from ``pos`` (the window clamped to end at m, lanes below
    ``pos`` masked), computes every (u, v) swap value with the host's
    arithmetic, and takes the first v with an improving, feasible u,
    then the first such u in X order: the host's (v, u) order. On a hit
    it swaps and the next trip starts at v + 1; otherwise at the end of
    the window. The loop state changes only on a swap, so this makes the
    host's decisions, bit for bit, in ~m/T + swaps trips a sweep.

    The matroid enters as ``mat``, its loop state, with
    ``swap_ok(mat, sel, w0, T)`` -> bool (kmax, T), whether slot j may
    leave for candidate w0 + t, and ``swap_mat(mat, sel2, uold, v)``,
    the state after the swap. Returns (sel, nsel, div, swaps, sweeps,
    trips), with the trips of all of this row's sweeps.
    """
    m = D.shape[0]
    T = _block_size(m, kmax)
    slots = jnp.arange(kmax, dtype=jnp.int32)
    lanes = jnp.arange(T, dtype=jnp.int32)

    def d_rows(w0, u):
        # D[u, w0:w0+T] for every slot: u is the row index, as the host reads
        return jax.vmap(
            lambda r: jax.lax.dynamic_slice(D, (r, w0), (1, T))[0])(u)

    def block_cond(carry):
        return carry[0] < m

    def block_body(carry):
        pos, trips, st = carry
        sel, selmask, mat, rowX, div, _swaps, _imp = st
        w0 = jnp.minimum(pos, m - T)
        u = jnp.maximum(sel, 0)  # (kmax,) slot -> local id (garbage past k)
        # div(X - u + v) = div - row[u] + dv - d(u, v)   (host's identity)
        new_div = ((div - rowX[u])[:, None]
                   + jax.lax.dynamic_slice(rowX, (w0,), (T,))[None, :]
                   - d_rows(w0, u))
        improving = (
            (slots < nsel)[:, None]
            & (new_div > div * (1.0 + gamma))
            & (new_div > div)
            & swap_ok(mat, sel, w0, T)
        )
        hit = (
            (w0 + lanes >= pos)
            & jax.lax.dynamic_slice(allow, (w0,), (T,))
            & ~jax.lax.dynamic_slice(selmask, (w0,), (T,))
            & jnp.any(improving, axis=0)
        )
        c = jnp.argmax(hit)  # first candidate of the block that swaps
        v = w0 + c
        ui = jnp.argmax(improving[:, c])  # first improving u in X order
        found = jnp.any(hit)

        def do_swap(st):
            sel, selmask, mat, _rowX, _div, swaps, _imp = st
            uold = sel[ui]
            # host order: X = [w for w in X if w != u] + [v]
            src = jnp.where(slots >= ui, jnp.minimum(slots + 1, kmax - 1),
                            slots)
            sel2 = sel[src].at[nsel - 1].set(v)
            selmask2 = selmask.at[uold].set(False).at[v].set(True)
            rowX2 = _dot(D, selmask2.astype(jnp.float32))
            return (sel2, selmask2, swap_mat(mat, sel2, uold, v), rowX2,
                    new_div[ui, c], swaps + 1, True)

        st = _cond_once(found, do_swap, st)
        return jnp.where(found, v + 1, w0 + T), trips + 1, st

    def sweep_cond(carry):
        st, sweeps, _trips = carry
        return st[-1] & (sweeps < max_sweeps)

    def sweep_body(carry):
        st, sweeps, trips = carry
        st = (*st[:-1], False)
        _pos, trips, st = jax.lax.while_loop(
            block_cond, block_body, (jnp.int32(0), trips, st))
        return st, sweeps + 1, trips

    selm_f = selmask.astype(jnp.float32)
    rowX0 = _dot(D, selm_f)
    div0 = 0.5 * _dot(selm_f, rowX0)
    ls0 = ((sel, selmask, mat, rowX0, div0, jnp.int32(0), nsel == k),
           jnp.int32(0), jnp.int32(0))
    (sel, _selmask, _mat, _rowX, div, swaps, _imp), sweeps, trips = (
        jax.lax.while_loop(sweep_cond, sweep_body, ls0)
    )
    return sel, nsel, div, swaps, sweeps, trips


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
    sel, selmask, counts, nsel = _greedy_seed(D, cats, caps, allow, k, kmax)

    def swap_ok(counts, sel, w0, T):
        u = jnp.maximum(sel, 0)
        cat_v = jax.lax.dynamic_slice(cats, (w0,), (T,))
        return (counts[cat_v][None, :] - (cats[u][:, None] == cat_v[None, :])
                + 1 <= caps[cat_v][None, :])

    def swap_mat(counts, sel2, uold, v):
        return counts.at[cats[uold]].add(-1).at[cats[v]].add(1)

    return _local_search(D, allow, k, gamma, sel, selmask, nsel, counts,
                         swap_ok, swap_mat, kmax=kmax, max_sweeps=max_sweeps)


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
    Returns (sel (B, kmax) local ids -1-padded, nsel (B,), div (B,),
    swaps, sweeps, trips) as ``vmap_rows`` counts them."""
    f = functools.partial(_solve_sum_one, kmax=kmax, max_sweeps=max_sweeps)
    with jax.named_scope("solver/jit_sum"):
        return vmap_rows(f, D, cats, caps, allow, ks, gammas)


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
    decision (feasibility truth is matching-independent). The loop state
    carries the matching and its ``swap_table``, rebuilt on each swap."""
    sel, selmask, ms_pt, nsel = _greedy_seed_tv(D, oh, allow, k, kmax)

    def swap_ok(mat, sel, w0, T):
        _ms, F = mat
        oh_v = jax.lax.dynamic_slice(oh, (w0, 0), (T, oh.shape[1]))
        # (kmax, h) . (h, T) over 0/1 entries: exact at any precision
        return jnp.dot(F.astype(jnp.float32), oh_v.T.astype(jnp.float32)) > 0

    def swap_mat(mat, sel2, uold, v):
        ms_pt, _F = mat
        # rebuild the matching: free u's category, re-insert v
        ms2 = jnp.where(ms_pt == uold, jnp.int32(-1), ms_pt)
        ms2 = augment(oh, ms2, v, kmax)
        return ms2, swap_table(oh, ms2, sel2)

    mat = (ms_pt, swap_table(oh, ms_pt, sel))
    return _local_search(D, allow, k, gamma, sel, selmask, nsel, mat,
                         swap_ok, swap_mat, kmax=kmax, max_sweeps=max_sweeps)


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
    Returns (sel (B, kmax) -1-padded, nsel (B,), div (B,), swaps, sweeps,
    trips) as ``vmap_rows`` counts them."""
    f = functools.partial(_solve_sum_one_tv, kmax=kmax, max_sweeps=max_sweeps)
    with jax.named_scope("solver/jit_sum_tv"):
        return vmap_rows(f, D, oh, allow, ks, gammas)


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
        tv = ctx.spec.kind == "transversal"
        with obs.span("solve.pack", cat="query", B=Bb, kmax=kmax, m=ctx.size):
            allow_b, ks, gammas = pad_query_arrays(ctx, specs, Bb)
            if tv:
                matroid = (cats_onehot(ctx.cats, ctx.spec.num_categories),)
            else:
                matroid = partition_arrays(ctx, specs, Bb)
        args = to_device(ctx.D, *matroid, allow_b, ks, gammas)
        fn, key = ((solve_sum_batch_transversal, "jit_sum_tv") if tv
                   else (solve_sum_batch, "jit_sum"))
        with obs.span("solve.run", cat="query") as run:
            with obs.compile_region(
                f"solve[{key} B={Bb} kmax={kmax} m={ctx.size}]"
            ):
                sel, nsel, _div, swaps, sweeps, trips = fn(*args, kmax=kmax)
            sel, nsel, swaps, sweeps, trips = jax.device_get(
                (sel, nsel, swaps, sweeps, trips)
            )
            run.update(swaps=int(swaps), sweeps=int(sweeps),
                       trips=int(trips),
                       visits=Bb * ctx.size * int(sweeps),
                       matroid=ctx.spec.kind)
        out = []
        with obs.span("solve.value", cat="query", n=len(specs)):
            for i, s in enumerate(specs):
                loc = sel[i, : nsel[i]].astype(np.int64)
                # the jit solver accumulates its objective in f32; the
                # indices are what it decided on — report the canonical
                # f64 value
                out.append(
                    EngineSolution(
                        local_indices=loc,
                        value=selection_value(ctx.D, loc, s.variant),
                        engine=self.name,
                    )
                )
        return out
