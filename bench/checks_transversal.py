"""The comparisons that decide ``correct`` for answered diversity queries
under a transversal matroid: what the timed path produced, against the
plain references (``bench/reference/transversal.py``) on the coreset the
reference scan made (the set-up's ``Catalog``, whose ``cats`` hold every
row's full label list and whose ``caps`` are None). Rows are matched by
stream index, never by the program's own numbering.

* ``epoch_mismatch`` and ``pdist_err``: as ``checks`` computes them.
* ``infeasible``: answers that repeat or invent a row, disagree with their
  own local indices, hold a row no allowed topic admits (a row passes the
  filter when any of its topics is allowed), are dependent (no matching
  gives each row a topic of its own), or fall short of min(k, the rank of
  the allowed rows).
* ``swap_gain``: over every answer that holds k rows, the largest relative
  gain in float64 diversity that one swap keeping the answer independent
  (a row of the answer out, an allowed row in) would bring; judged this
  way, and not by equality with the reference's answer, for the reason
  ``checks`` gives.
"""
from __future__ import annotations

import numpy as np

import checks
from reference import local_search, transversal


def allow_mask(cats: np.ndarray, allowed) -> np.ndarray:
    if allowed is None:
        return np.ones(cats.shape[0], bool)
    hit = np.isin(cats, np.fromiter(allowed, np.int64, len(allowed)))
    return (hit & (cats >= 0)).any(axis=1)


def infeasible_answers(served, served_src, catalog) -> int:
    pos = checks.position(catalog)
    cats = catalog.cats
    src = np.asarray(served_src, np.int64)
    ranks: dict = {}  # rank of the allowed rows, per filter
    bad = 0
    for s in served:
        r = s.result
        if r is None:
            continue
        loc = np.asarray(r.local_indices, np.int64)
        sel = checks.picked_positions(r, pos)
        allow = allow_mask(cats, s.q.allowed)
        ok = (
            loc.size == sel.size
            and np.all((loc >= 0) & (loc < src.size))
            and np.array_equal(src[np.clip(loc, 0, max(0, src.size - 1))],
                               np.asarray(r.indices, np.int64))
            and len(set(sel.tolist())) == sel.size
            and np.all(sel >= 0)
        )
        if ok:
            if s.q.allowed not in ranks:
                ranks[s.q.allowed] = transversal.rank(cats,
                                                      np.flatnonzero(allow))
            ok = (
                bool(np.all(allow[sel]))
                and transversal.independent(cats, sel)
                and sel.size == min(s.q.k, ranks[s.q.allowed])
            )
        bad += not ok
    return bad


def swap_gain(D: np.ndarray, cats: np.ndarray, oh: np.ndarray, sel,
              allow: np.ndarray) -> float:
    """Largest relative gain in diversity on ``D`` of one swap that keeps
    ``sel`` independent (a row of ``sel`` out, an allowed row outside it
    in); 0 when none improves."""
    X = np.asarray(sel, np.int64)
    if X.size < 2:
        return 0.0
    DX = D[:, X]  # (m, k)
    row = DX[X].sum(axis=1)  # row[j]: distances of X[j] to X
    div = row.sum() / 2.0
    # diversity of X - X[j] + v, for every candidate v (rows) and j (cols)
    new = div - row[None, :] + DX.sum(axis=1)[:, None] - DX
    fits, _ = transversal.swap_table(cats, oh, X)
    out = allow.copy()
    out[X] = False
    ok = fits & out[:, None]
    if div <= 0 or not ok.any():
        return 0.0
    return float(max(0.0, (new[ok] - div).max()) / div)


def answer_numbers(served, catalog, D_ref, h: int, D_solve=None) -> dict:
    """``swap_gain`` over every answer that holds k rows (``D_solve``: the
    reference local search on that matrix stands in for the program's
    selections: the control)."""
    pos = checks.position(catalog)
    cats = catalog.cats
    oh = transversal.onehot(cats, h)
    gain = 0.0
    for s in served:
        if s.result is None:
            continue
        allow = allow_mask(cats, s.q.allowed)
        if D_solve is None:
            got = checks.picked_positions(s.result, pos)
        else:
            got = np.asarray(transversal.solve(D_solve, cats, h, s.q.k,
                                               allow), np.int64)
        if (got.size < s.q.k or np.any(got < 0)
                or not transversal.independent(cats, got)):
            continue  # greedy only, outside the coreset, or dependent:
            # ``infeasible`` counts it
        gain = max(gain, swap_gain(D_ref, cats, oh, got, allow))
    return {"swap_gain": gain}


def query_numbers(svc, catalog, served, h: int) -> dict:
    snap = svc.runtime.latest()
    src = np.asarray(snap.src_idx, np.int64)
    D_ref = local_search.pdist(catalog.rows)
    entry = svc.cache.lookup(svc.cache_key, snap.fingerprint)
    return {
        "epoch_mismatch": checks.epoch_mismatch(src, catalog),
        "pdist_err": (checks.pdist_err(entry.D, src, catalog, D_ref)
                      if entry is not None else checks.MISSING),
        "infeasible": float(infeasible_answers(served, src, catalog)),
        **answer_numbers(served, catalog, D_ref, h),
    }
