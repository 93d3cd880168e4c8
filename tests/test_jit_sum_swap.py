"""The ``jit_sum`` local search: its block scan and its swap branch.

Each sweep tests the candidates a block of T at a time and takes the
first improving (v, u) in the host's scan order; the next trip starts
after the swap's v. The swap (new selection, counts or matching, and the
full ``D @ X`` row-sum recompute) runs under a single-trip
``while_loop``, so a batched solve reads D for a swap only on the trips
where some row swaps. A plain ``lax.cond`` there (the select form)
lowers under vmap to a select that runs the swap on every trip. Pinned
here:

* the jaxpr keeps every matmul of D in the block loop inside a nested
  ``while`` (partition and transversal, B 1 and 4);
* answers, and the ``swaps``/``sweeps``/``trips`` counters of
  ``solve.run``, equal the select form's and the host local search's
  (every launch path), where the trips are the blocks the host's swap
  sequence walks through, at block boundaries chosen case by case;
* the counters read the host local search's swap count, 0 swaps and one
  sweep where the greedy seed is already a local optimum;
* the transversal swap test read from ``matching.swap_table`` equals
  ``matching.swap_feasible`` for every candidate and slot.
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
from repro.core.solvers.jit_sum import bucket_pow2
from repro.core.solvers.local_search import greedy_init, local_search_sum
from repro.core.solvers.matching import swap_feasible, swap_table

M = 40  # no other pair of axes of the jaxpr guard's solves is 40 x 40


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
    """Swap the single-trip loop for a plain ``lax.cond``."""
    monkeypatch.setattr(
        jit_sum, "_cond_once",
        lambda pred, fn, st: jax.lax.cond(pred, fn, lambda s: s, st))
    _fresh_jits(monkeypatch)


def _blocks(monkeypatch, T):
    """Cut the block loop's trips to T candidates (at most m)."""
    monkeypatch.setattr(jit_sum, "_block_size", lambda m, kmax: min(T, m))
    _fresh_jits(monkeypatch)


def _fresh_jits(monkeypatch):
    """Give each batch solver a fresh jit (new function objects, so no
    trace made before a patch is reused)."""
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


def _candidate_loops(jaxpr, in_while=False):
    """The bodies of the candidate loops: each ``while`` nested in a
    ``while`` (the block loop in the sweep loop). The walk stops there,
    so the swap's own single-trip ``while`` inside it is not counted."""
    for e in jaxpr.eqns:
        if e.primitive.name == "while" and in_while:
            yield e.params["body_jaxpr"].jaxpr
            continue
        for sub in _subjaxprs(e):
            yield from _candidate_loops(
                sub, in_while or e.primitive.name == "while")


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
        _solver_jaxpr(getattr(jit_sum, name), tv, B)))
    assert len(loops) == 1
    assert [_exposed_dots(body) for body in loops] == [0]
    # the walk sees the matmul where the select form puts it
    _select_form(monkeypatch)
    loops = list(_candidate_loops(
        _solver_jaxpr(getattr(jit_sum, name), tv, B)))
    assert [_exposed_dots(body) > 0 for body in loops] == [True]


# --------------------------------------------------------------------------
# bit identity against the select form and the host local search
# --------------------------------------------------------------------------


def _run_args(spans):
    return [(s.args["swaps"], s.args["sweeps"], s.args["trips"])
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


class _Recorded:
    """A host matroid that records the v of every swap the host local
    search makes (its independence test passes only for a swap)."""

    def __init__(self, inner):
        self.inner, self.vs = inner, []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def is_independent(self, X):
        ok = self.inner.is_independent(X)
        if ok:
            self.vs.append(X[-1])
        return ok


def _host(ctx, spec):
    """(X, swaps, the v of each swap in order) of the host local search."""
    mat = _Recorded(ctx.matroid_fn(spec))
    idxs = [int(i) for i in np.flatnonzero(spec.allow_mask(ctx.size))]
    X, _val, swaps = local_search_sum(
        ctx.D, mat, spec.k, idxs, gamma=spec.gamma)
    return X, swaps, mat.vs


def _walk(m, T, vs):
    """(sweeps, trips) of the block loop on one row whose host local
    search swapped at ``vs``, in order: a sweep's swaps come at rising v,
    and one last sweep finds none. Each trip is (w0, pos, v): the window
    from w0 (clamped to end at m), the first candidate it may take, and
    the v it swaps at (None: no swap, the next trip starts at w0 + T)."""
    sweeps = [[]]
    for v in vs:
        if sweeps[-1] and v <= sweeps[-1][-1]:
            sweeps.append([])
        sweeps[-1].append(v)
    if vs:
        sweeps.append([])
    trips = []
    for sweep in sweeps:
        pos, todo = 0, list(sweep)
        while pos < m:
            w0 = min(pos, m - T)
            v = todo.pop(0) if todo and todo[0] < w0 + T else None
            trips.append((w0, pos, v))
            pos = w0 + T if v is None else v + 1
    return len(sweeps), trips


def _expected_counters(lanes, host):
    """The launch's (swaps, sweeps, trips) from the host's swaps: rows
    padded to a power of two (k=0 rows sweep once, swapping nothing),
    rows short of k never sweep, each lane reads its rows' most, and the
    stacked launch sums its lanes (padded to a power of two too)."""
    m = lanes[0][0].size
    kmax = bucket_pow2(max(s.k for _ctx, specs in lanes for s in specs))
    T = jit_sum._block_size(m, kmax)
    Bb = bucket_pow2(max(len(specs) for _ctx, specs in lanes))
    rows = iter(host)
    walks = []
    for _ctx, specs in lanes:
        lane = [_walk(m, T, vs) if len(X) == s.k else (0, [])
                for s, (X, _swaps, vs) in zip(specs, rows)]
        lane += [_walk(m, T, [])] * (Bb - len(specs))
        walks.append((max(n for n, _t in lane),
                      max(len(t) for _n, t in lane)))
    walks += [(1, len(_walk(m, T, [])[1]))] * (
        bucket_pow2(len(lanes)) - len(lanes))
    swaps = sum(s for _X, s, _vs in host)
    if len(lanes) == 1:
        return swaps, *walks[0]
    return swaps, sum(n for n, _t in walks), sum(t for _n, t in walks)


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
    if name.startswith("stacked-T2-B2-k8"):
        return "stacked", [
            (_ctx("partition", 2), [SolveSpec(k=5), SolveSpec(k=8)]),
            (_ctx("uniform", 5), [SolveSpec(k=4), SolveSpec(k=6)])]
    # the block scan's edges; the case's block size, where set, stands in
    # for the kmax rule's (it is cut to m either way)
    if name == "uniform-B1-k12-m40-T16":
        return _ctx("uniform", 37), [SolveSpec(k=12)]
    if name == "uniform-B1-k8-m100":
        return _ctx("uniform", 2, m=100), [SolveSpec(k=8)]
    if name == "partition-B1-k8-T8":
        return _ctx("partition", 20), [SolveSpec(k=8)]
    if name == "partition-B1-k8-T16":
        return _ctx("partition", 16), [SolveSpec(k=8)]
    if name == "partition-B1-k8-T4-filtered":
        allow = np.ones(M, bool)
        allow[8:20] = False
        return _ctx("partition", 4), [SolveSpec(k=8, allow=allow)]
    if name == "transversal-B1-k24-h40-m300":
        return _ctx("transversal", 3, m=300, h=20), [SolveSpec(k=24)]
    raise ValueError(name)


def _last_lane(trips, T, m, allow):
    """A swap at the last candidate of a block that is not the last."""
    return any(v == w0 + T - 1 < m - 1 for w0, _pos, v in trips)


def _two_in_one_block(trips, T, m, allow):
    """A swap, then the next trip's swap inside the first one's block."""
    return any(a[2] is not None and b[2] is not None and b[1] == a[2] + 1
               and b[2] < a[0] + T for a, b in zip(trips, trips[1:]))


def _clamped(trips, T, m, allow):
    """m is no multiple of T: a swap in a window clamped to end at m."""
    return m % T and any(v is not None and w0 < pos for w0, pos, v in trips)


def _empty_blocks(trips, T, m, allow):
    """The filter leaves some whole trip with no candidate to test."""
    return any(not allow[max(w0, pos):w0 + T].any() for w0, pos, _v in trips)


def _one_block(trips, T, m, allow):
    """The block covers all m candidates: m is below the kmax rule's
    power of two, so the block is cut to m."""
    return T == m


# name -> (block size or None for the kmax rule, what the case shows)
CASES = {
    "uniform-B1-k4": (None, None),
    "uniform-B4-k16": (None, None),
    "partition-B1-k8": (None, None),
    "partition-B4-k8": (None, None),
    "transversal-B1-k4": (None, None),
    "transversal-B4-k8": (None, None),
    "stacked-T2-B2-k8": (None, None),
    "uniform-B1-k12-m40-T16": (16, _clamped),
    "uniform-B1-k8-m100": (None, _one_block),
    "partition-B1-k8-T8": (8, _last_lane),
    "partition-B1-k8-T16": (16, _two_in_one_block),
    "partition-B1-k8-T4-filtered": (4, _empty_blocks),
    "transversal-B1-k24-h40-m300": (None, _clamped),
    "stacked-T2-B2-k8-T8": (8, None),
}


@pytest.mark.parametrize("name", list(CASES))
def test_answers_match_the_select_form_and_the_host(monkeypatch, name):
    T, premise = CASES[name]
    if T is not None:
        _blocks(monkeypatch, T)
    case = _case(name)
    got, counters = _solve(case)
    lanes = case[1] if case[0] == "stacked" else [case]
    host = [_host(ctx, spec) for ctx, specs in lanes for spec in specs]
    assert got == [X for X, _swaps, _vs in host]
    [(swaps, sweeps, trips)] = counters
    assert swaps > 0
    assert (swaps, sweeps, trips) == _expected_counters(lanes, host)
    if premise is not None:
        ctx, [spec, *_] = lanes[0]
        m = ctx.size
        T = jit_sum._block_size(m, bucket_pow2(spec.k))
        assert premise(_walk(m, T, host[0][2])[1], T, m, spec.allow_mask(m))
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
    X, host_swaps, _vs = _host(ctx, spec)
    seed_X = greedy_init(ctx.D, ctx.matroid_fn(spec), k, range(ctx.size))
    assert (X == seed_X) == local_optimum
    got, [(swaps, sweeps, _trips)] = _solve((ctx, [spec]))
    assert got == [X]
    assert swaps == host_swaps
    if local_optimum:
        assert (swaps, sweeps) == (0, 1)
    else:
        assert swaps > 0 and sweeps >= 2


# --------------------------------------------------------------------------
# the transversal swap test: one table per swap, exact
# --------------------------------------------------------------------------


def _random_matching(seed, m, h, kmax):
    """A random complete matching of a random selection of up to
    min(kmax, h - 1, m) points, and a random multilabel incidence over
    the matched categories and one free one: a selected point holds its
    matched category and, half the time, one more; any other point 1 to
    3. Alternating paths of several steps then decide, and some swaps
    are feasible and some not. Returns (oh, ms_pt, sel -1-padded to
    kmax)."""
    r = np.random.default_rng(seed)
    n = int(r.integers(1, min(kmax, h - 1, m) + 1))
    pool = r.choice(h, n + 1, replace=False)
    pts = r.choice(m, n, replace=False)
    oh = np.zeros((m, h), bool)
    for p in range(m):
        oh[p, r.choice(pool, r.integers(1, 4), replace=False)] = True
    oh[pts] = False
    oh[pts, pool[:n]] = True
    half = r.random(n) < 0.5
    oh[pts[half], r.choice(pool, half.sum())] = True
    ms_pt = np.full(h, -1, np.int32)
    ms_pt[pool[:n]] = pts
    sel = np.full(kmax, -1, np.int32)
    sel[:n] = r.permutation(pts)
    return oh, ms_pt, sel


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kmax", [4, 16, 128])
def test_swap_table_is_swap_feasible_for_every_candidate(kmax, seed):
    oh, ms_pt, sel = _random_matching(seed + 100 * kmax, 24, 100, kmax)
    F = swap_table(jnp.asarray(oh), jnp.asarray(ms_pt), jnp.asarray(sel))
    got = (oh.astype(np.float32) @ np.asarray(F).T.astype(np.float32)) > 0
    want = jax.jit(jax.vmap(swap_feasible, in_axes=(None, None, None, 0)))(
        oh, ms_pt, sel, jnp.arange(24))
    assert got.shape == (24, kmax)
    np.testing.assert_array_equal(got, np.asarray(want))
    # the draws reach both answers among the selected slots
    n = int((sel >= 0).sum())
    assert got[:, :n].any() and not got[:, :n].all()
