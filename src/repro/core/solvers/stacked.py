"""Cross-tenant stacked solves: one device dispatch for a mixed window.

Tenants of one serving frontend answer over the *same* published epoch:
their cache entries share the coreset rows and differ only in the pdist
matrix (metric normalization) and the matroid view (cats/caps). For the
counts-family ``jit_sum`` kernel every vmapped row is already
composition-independent — a row's greedy + local-search decisions read
only its own ``(D, cats, caps, allow, k, gamma)`` leaves — so a window
holding queries for several tenants can legally execute as ONE stacked
launch with a batched pdist leaf instead of one launch per tenant. That
is §3 composability pointed at the solve dispatch: the per-call overhead
the coalescer amortizes across callers, this module amortizes across
tenants.

Bit-identity (the parity contract ``tests/test_stacked_solve.py`` pins):
the stacked kernel is a ``lax.scan`` over tenant lanes whose body is the
per-tenant row solver itself (``jit_sum._solve_sum_one``, whose swap runs
only when some row of the lane swaps) vmapped by the same
``jit_sum.vmap_rows``, with an unmapped ``(m, m)`` D — each scan step
slices one tenant's matrix out of the batched leaf, so every matmul runs
at the same shape and accumulation as the per-tenant dispatch. (A gather-form
``vmap(f(Ds[t], ...))`` was measurably NOT safe: the batched matmul
accumulates in a different order and flips greedy argmax decisions on
tie-heavy data.) The remaining freedom — the pow-2 row padding differing
from what per-tenant dispatch would pick — is exactly the freedom the
shipped coalescer already exercises, and the same parity suites pin it.

Scope: ``variant="sum"`` under uniform/partition matroids (the counts
``counts < caps`` feasibility path). Transversal lanes carry a
per-tenant one-hot incidence whose width varies; host engines have no
batched kernel at all — both fall back to per-tenant dispatch in the
frontend.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from .base import (
    EngineSolution,
    SolveContext,
    SolveSpec,
    SolverEngine,
    selection_value,
)
from .jit_sum import (
    _solve_sum_one,
    bucket_pow2,
    jit_cell_eligible,
    to_device,
    vmap_rows,
)

# one tenant lane of a stacked solve: (context, specs routed to it)
Lane = tuple[SolveContext, Sequence[SolveSpec]]


def counts_stack_eligible(
    engine: SolverEngine, ctx: SolveContext, spec: SolveSpec
) -> bool:
    """Can this request ride a stacked counts-family launch?  The jit
    cell eligibility rules apply unchanged; transversal is excluded
    because its one-hot incidence width is a per-tenant static shape."""
    if ctx.spec.kind not in ("uniform", "partition"):
        return False
    return jit_cell_eligible(engine, ctx, spec)


@functools.partial(jax.jit, static_argnames=("kmax", "max_sweeps"))
def solve_sum_batch_stacked(
    Ds: jnp.ndarray,  # (T, m, m) per-lane cached distances
    cats_s: jnp.ndarray,  # (T, m) int32 single-label categories
    caps: jnp.ndarray,  # (T, Bt, h) per-row caps
    allow: jnp.ndarray,  # (T, Bt, m) per-row candidate masks
    ks: jnp.ndarray,  # (T, Bt)
    gammas: jnp.ndarray,  # (T, Bt)
    *,
    kmax: int,
    max_sweeps: int = 64,
):
    """T tenant lanes of Bt sum-DMMC rows each, ONE dispatch.  Returns
    (sel (T, Bt, kmax) -1-padded, nsel (T, Bt), div (T, Bt), swaps,
    sweeps, trips): the swaps of all rows, and the sweep-loop and
    block-loop trips of all lanes.

    ``lax.scan`` (not an outer vmap) on purpose: inside each scan step
    the lane's D is a concrete (m, m) operand, so the inner vmapped
    solver lowers to the very same unbatched-matrix HLO as the
    per-tenant ``solve_sum_batch`` — which is what makes the per-row
    results bit-identical rather than merely close.
    """
    f = functools.partial(_solve_sum_one, kmax=kmax, max_sweeps=max_sweeps)

    def lane(carry, xs):
        return carry, vmap_rows(f, *xs)

    with jax.named_scope("solver/jit_sum_stacked"):
        _, (sel, nsel, div, swaps, sweeps, trips) = jax.lax.scan(
            lane, jnp.int32(0), (Ds, cats_s, caps, allow, ks, gammas)
        )
    return sel, nsel, div, jnp.sum(swaps), jnp.sum(sweeps), jnp.sum(trips)


def solve_stacked(lanes: Sequence[Lane]) -> list[list[EngineSolution]]:
    """Execute several single-tenant spec groups as one stacked launch.

    Every lane must be counts-stack eligible (caller's responsibility —
    see ``counts_stack_eligible``) and share the coreset size and D
    dtype. Shapes bucket to powers of two independently per axis
    (lanes T, rows-per-lane Bt, kmax), so the compile cache is keyed the
    same way the per-tenant kernel's is. Returns per-lane solution
    lists in lane order.
    """
    if not lanes:
        return []
    m = lanes[0][0].size
    dtype = np.asarray(lanes[0][0].D).dtype
    for ctx, _specs in lanes:
        if ctx.size != m:
            raise ValueError(
                f"stacked lanes must share the coreset size: {ctx.size} != {m}"
            )
        if np.asarray(ctx.D).dtype != dtype:
            raise ValueError(
                "stacked lanes must share the distance dtype: "
                f"{np.asarray(ctx.D).dtype} != {dtype}"
            )
    T = len(lanes)
    Tb = bucket_pow2(T)
    Bt = bucket_pow2(max(len(specs) for _ctx, specs in lanes))
    kmax = bucket_pow2(
        max((s.k for _ctx, specs in lanes for s in specs), default=1)
    )
    hs = [
        ctx.spec.num_categories if ctx.spec.kind == "partition" else 1
        for ctx, _specs in lanes
    ]
    hmax = max(hs)
    with obs.span("solve.pack", cat="query", T=Tb, B=Bt, kmax=kmax, m=m):
        # padding lanes keep a zero matrix and k=0 rows: the row solver
        # no-ops on them exactly like the pow-2 padding rows it already has
        Ds = np.zeros((Tb, m, m), dtype)
        cats_s = np.zeros((Tb, m), np.int32)
        caps = np.full((Tb, Bt, hmax), m + 1, np.int32)  # padding: uncapped
        allow = np.zeros((Tb, Bt, m), bool)
        ks = np.zeros((Tb, Bt), np.int32)
        gammas = np.zeros((Tb, Bt), np.float32)
        for t, (ctx, specs) in enumerate(lanes):
            Ds[t] = ctx.D
            if ctx.spec.kind == "partition":
                cats_s[t] = np.asarray(ctx.cats[:, 0], np.int32)
                default_caps = ctx.caps
            else:  # uniform: one pseudo-category nobody caps
                default_caps = None
            h = hs[t]
            for i, s in enumerate(specs):
                allow[t, i] = s.allow_mask(m)
                ks[t, i] = s.k
                gammas[t, i] = s.gamma
                if s.caps is not None:
                    caps[t, i, :h] = np.asarray(s.caps, np.int32)
                elif default_caps is not None:
                    caps[t, i, :h] = default_caps
    args = to_device(Ds, cats_s, caps, allow, ks, gammas)
    with obs.span("solve.run", cat="query") as run:
        with obs.compile_region(
            f"solve[jit_sum_stacked T={Tb} B={Bt} kmax={kmax} m={m}]"
        ):
            sel, nsel, _div, swaps, sweeps, trips = solve_sum_batch_stacked(
                *args, kmax=kmax
            )
        sel, nsel, swaps, sweeps, trips = jax.device_get(
            (sel, nsel, swaps, sweeps, trips))
        # each lane's loop runs its Bt rows over all m candidates per sweep
        run.update(swaps=int(swaps), sweeps=int(sweeps), trips=int(trips),
                   visits=Bt * m * int(sweeps),
                   matroid=",".join(sorted({ctx.spec.kind
                                            for ctx, _specs in lanes})))
    out: list[list[EngineSolution]] = []
    with obs.span("solve.value", cat="query",
                  n=sum(len(specs) for _ctx, specs in lanes)):
        for t, (ctx, specs) in enumerate(lanes):
            sols = []
            for i, s in enumerate(specs):
                loc = sel[t, i, : nsel[t, i]].astype(np.int64)
                # same contract as the per-tenant engine: the f32 objective
                # the kernel accumulated is discarded, the canonical f64
                # value is recomputed from the indices it decided on
                sols.append(
                    EngineSolution(
                        local_indices=loc,
                        value=selection_value(ctx.D, loc, s.variant),
                        engine="jit_sum",
                    )
                )
            out.append(sols)
    return out
