"""The ``jit_sum`` local search's swap branch: a real branch under vmap.

The swap (new selection, counts or matching, and the full ``D @ X``
row-sum recompute) runs under a single-trip ``while_loop``, so a batched
solve reads D for a swap only on the candidates where some row swaps. A
plain ``lax.cond`` there (the select form) lowers under vmap to a select
that runs the swap for every candidate of every sweep. Pinned here:

* the jaxpr keeps every matmul of D in the per-candidate loop inside a
  nested ``while`` (partition and transversal, B 1 and 4);
* answers, and the ``swaps``/``sweeps`` counters of ``solve.run``, equal
  the select form's and the host local search's (every launch path);
* the counters read the host local search's swap count, 0 swaps and one
  sweep where the greedy seed is already a local optimum.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.matroid import MatroidSpec, make_host_matroid
from repro.core.solvers import SolveContext, SolveSpec, get_engine, jit_sum
from repro.core.solvers import stacked
from repro.core.solvers.local_search import greedy_init, local_search_sum

M = 40  # no other axis of the solves below is 40 long


def _ctx(kind, seed, m=M, h=4, cap=3):
    r = np.random.default_rng(seed)
    pts = r.random((m, 3))
    D = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1)).astype(np.float32)
    np.fill_diagonal(D, 0.0)
    if kind == "uniform":
        spec, cats, caps = MatroidSpec("uniform"), None, None
    elif kind == "partition":
        spec = MatroidSpec("partition", num_categories=h, gamma=1)
        cats = r.integers(0, h, (m, 1)).astype(np.int32)
        caps = np.full(h, cap, np.int32)
    else:
        spec = MatroidSpec("transversal", num_categories=2 * h, gamma=2)
        cats = np.full((m, 2), -1, np.int32)
        cats[:, 0] = r.integers(0, 2 * h, m)
        extra = r.random(m) < 0.4
        cats[extra, 1] = r.integers(0, 2 * h, extra.sum())
        caps = None
    return SolveContext(
        D=D, spec=spec, cats=cats, caps=caps,
        matroid_fn=lambda s: make_host_matroid(
            spec, cats, caps if s.caps is None else np.asarray(s.caps),
            m, s.k),
    )


def _select_form(monkeypatch):
    """Swap the single-trip loop for a plain ``lax.cond``, and give
    each batch solver a fresh jit (new function objects, so no trace
    made before the patch is reused)."""
    monkeypatch.setattr(
        jit_sum, "_cond_once",
        lambda pred, fn, st: jax.lax.cond(pred, fn, lambda s: s, st))
    for mod, name in ((jit_sum, "solve_sum_batch"),
                      (jit_sum, "solve_sum_batch_transversal"),
                      (stacked, "solve_sum_batch_stacked")):
        raw = getattr(mod, name).__wrapped__
        monkeypatch.setattr(mod, name, jax.jit(
            lambda *a, _raw=raw, **kw: _raw(*a, **kw),
            static_argnames=("kmax", "max_sweeps")))


# --------------------------------------------------------------------------
# guard: no matmul of D on the per-candidate path
# --------------------------------------------------------------------------


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in v if isinstance(v, (list, tuple)) else [v]:
            if hasattr(sub, "eqns"):
                yield sub
            elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                yield sub.jaxpr


def _candidate_loops(jaxpr, m, in_while=False):
    """The m-long ``scan``s (the candidate ``fori_loop``) nested in a
    ``while`` (the sweep loop)."""
    for e in jaxpr.eqns:
        if e.primitive.name == "scan" and in_while and e.params["length"] == m:
            yield e.params["jaxpr"].jaxpr
            continue
        for sub in _subjaxprs(e):
            yield from _candidate_loops(
                sub, m, in_while or e.primitive.name == "while")


def _exposed_dots(jaxpr, m=M):
    """``dot_general``s of ``jaxpr`` with the (m, m) D as an operand and
    not inside a nested ``while`` (the transversal feasibility test's
    small category products are not counted)."""
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            n += any(v.aval.shape[-2:] == (m, m) for v in e.invars)
        elif e.primitive.name != "while":
            n += sum(_exposed_dots(sub, m) for sub in _subjaxprs(e))
    return n


def _solver_jaxpr(fn, tv, B, kmax=4, m=M):
    D = jnp.zeros((m, m), jnp.float32)
    rows = (jnp.zeros((B, m), bool), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.float32))
    if tv:
        args = (D, jnp.zeros((m, 8), bool), *rows)
    else:
        args = (D, jnp.zeros((m,), jnp.int32), jnp.zeros((B, 4), jnp.int32),
                *rows)
    return jax.make_jaxpr(functools.partial(fn, kmax=kmax))(*args).jaxpr


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("name", ["solve_sum_batch",
                                  "solve_sum_batch_transversal"])
def test_swap_matmul_is_off_the_per_candidate_path(monkeypatch, name, B):
    tv = name.endswith("transversal")
    loops = list(_candidate_loops(
        _solver_jaxpr(getattr(jit_sum, name), tv, B), M))
    assert len(loops) == 1
    assert [_exposed_dots(body) for body in loops] == [0]
    # the walk sees the matmul where the select form puts it
    _select_form(monkeypatch)
    loops = list(_candidate_loops(
        _solver_jaxpr(getattr(jit_sum, name), tv, B), M))
    assert [_exposed_dots(body) > 0 for body in loops] == [True]


# --------------------------------------------------------------------------
# bit identity against the select form and the host local search
# --------------------------------------------------------------------------


def _run_args(spans):
    return [(s.args["swaps"], s.args["sweeps"])
            for s in spans if s.name == "solve.run"]


def _solve(case):
    """(per-row local indices, the launch's (swaps, sweeps)) of a case."""
    buf = obs.default_buffer()
    buf.clear()
    if case[0] == "stacked":
        lanes = case[1]
        sols = [s for lane in stacked.solve_stacked(lanes) for s in lane]
    else:
        ctx, specs = case
        sols = get_engine("jit_sum").solve_batch(ctx, specs)
    return [s.local_indices.tolist() for s in sols], _run_args(buf.drain())


def _host(ctx, spec):
    idxs = [int(i) for i in np.flatnonzero(spec.allow_mask(ctx.size))]
    X, _val, swaps = local_search_sum(
        ctx.D, ctx.matroid_fn(spec), spec.k, idxs, gamma=spec.gamma)
    return X, swaps


def _case(name):
    r = np.random.default_rng(11)
    if name == "uniform-B1-k4":
        return _ctx("uniform", 3), [SolveSpec(k=4)]
    if name == "uniform-B4-k16":
        return _ctx("uniform", 5), [SolveSpec(k=k) for k in (3, 9, 12, 16)]
    if name == "partition-B1-k8":
        return _ctx("partition", 2), [SolveSpec(k=8)]
    if name == "partition-B4-k8":
        return _ctx("partition", 2), [
            SolveSpec(k=2), SolveSpec(k=5, allow=r.random(M) < 0.7),
            SolveSpec(k=7, caps=(2, 2, 2, 2)), SolveSpec(k=8)]
    if name == "transversal-B1-k4":
        return _ctx("transversal", 9), [SolveSpec(k=4)]
    if name == "transversal-B4-k8":
        return _ctx("transversal", 9), [
            SolveSpec(k=k) for k in (2, 3, 5, 6)]
    if name == "stacked-T2-B2-k8":
        return "stacked", [
            (_ctx("partition", 2), [SolveSpec(k=5), SolveSpec(k=8)]),
            (_ctx("uniform", 5), [SolveSpec(k=4), SolveSpec(k=6)])]
    raise ValueError(name)


@pytest.mark.parametrize("name", [
    "uniform-B1-k4", "uniform-B4-k16", "partition-B1-k8", "partition-B4-k8",
    "transversal-B1-k4", "transversal-B4-k8", "stacked-T2-B2-k8"])
def test_answers_match_the_select_form_and_the_host(monkeypatch, name):
    case = _case(name)
    got, counters = _solve(case)
    lanes = case[1] if case[0] == "stacked" else [case]
    host = [_host(ctx, spec) for ctx, specs in lanes for spec in specs]
    assert got == [X for X, _swaps in host]
    [(swaps, _sweeps)] = counters
    assert swaps == sum(s for _X, s in host) > 0
    _select_form(monkeypatch)
    assert _solve(case) == (got, counters)


# --------------------------------------------------------------------------
# the solve.run counters
# --------------------------------------------------------------------------


@pytest.mark.parametrize("seed,k,local_optimum", [(1, 3, False),
                                                  (4, 8, False),
                                                  (0, 5, True),
                                                  (6, 8, True)])
def test_solve_run_counts_swaps_and_sweeps(seed, k, local_optimum):
    ctx = _ctx("partition", seed, m=32, cap=2)
    spec = SolveSpec(k=k)
    X, host_swaps = _host(ctx, spec)
    seed_X = greedy_init(ctx.D, ctx.matroid_fn(spec), k, range(ctx.size))
    assert (X == seed_X) == local_optimum
    got, [(swaps, sweeps)] = _solve((ctx, [spec]))
    assert got == [X]
    assert swaps == host_swaps
    if local_optimum:
        assert (swaps, sweeps) == (0, 1)
    else:
        assert swaps > 0 and sweeps >= 2
