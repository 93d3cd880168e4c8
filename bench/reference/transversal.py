"""Plain reference of the sum-diversity query under a transversal matroid:
an independence oracle, the rank of a row set, the swaps that keep a set
independent, and greedy seeding plus AMT first-improvement local search
(arXiv:2002.03175 section 4.1 footnote 5, gamma = 0) with an optional
candidate filter. numpy float64; imports nothing of the program.

A row holds up to gamma topic labels (``cats``: int[m, gamma], -1 padded)
and a set of rows is independent iff each row can be matched to a distinct
label of its own (a matching that covers the set). ``match`` finds a
maximum matching by augmenting paths (Kuhn's algorithm).

Swaps. Let X be independent with a covering matching M. An alternating
path from a row v enters a label c of v; if c is free in M the path ends
(X + v is independent), else it goes on through the row M matches to c to
another label of that row. X - u + v is independent iff such a path from v
reaches a free label or the label M gives u: a path to u's label ends
there once u leaves, and it reaches it before it could use u's own edge.
``swap_table`` answers this for every (v, u) at once, from the
reflexive-transitive closure of the label graph that M induces.

Greedy: repeatedly add the allowed candidate outside the set that keeps it
independent with the largest sum of distances to the rows already chosen
(the first pick takes the largest row sum over all rows); ties go to the
lowest index. On a matroid it stops at min(k, rank of the allowed rows).

Local search, one sweep (the order ``local_search.py`` documents): for
every allowed candidate v outside X in index order, take the first u in
X's order such that X - u + v is independent and has strictly larger
diversity; X becomes X - u + v (v appended last) and the sweep moves on to
the next v. Sweeps repeat until one swaps nothing, at most 64. A set the
greedy seed leaves short of k rows is not searched.
"""
from __future__ import annotations

import numpy as np


def labels(cats: np.ndarray, r: int) -> list[int]:
    """Distinct labels of row r, in its order."""
    return list(dict.fromkeys(int(c) for c in cats[r] if c >= 0))


def match(cats: np.ndarray, rows) -> dict:
    """A maximum matching of ``rows`` into their labels: {label: row}."""
    owner: dict = {}

    def augment(r, seen) -> bool:
        for c in labels(cats, r):
            if c in seen:
                continue
            seen.add(c)
            if c not in owner or augment(owner[c], seen):
                owner[c] = r
                return True
        return False

    for r in rows:
        augment(int(r), set())
    return owner


def independent(cats: np.ndarray, rows) -> bool:
    rows = [int(r) for r in rows]
    return len(set(rows)) == len(rows) and len(match(cats, rows)) == len(rows)


def rank(cats: np.ndarray, rows) -> int:
    """Size of the largest independent subset of ``rows``."""
    return len(match(cats, rows))


def onehot(cats: np.ndarray, h: int) -> np.ndarray:
    """bool[m, h]: row r holds label c."""
    oh = np.zeros((cats.shape[0], h), bool)
    r, j = np.nonzero(cats >= 0)
    oh[r, cats[r, j]] = True
    return oh


def _closure(A: np.ndarray) -> np.ndarray:
    """Reflexive-transitive closure of a boolean square matrix."""
    T = A | np.eye(A.shape[0], dtype=bool)
    while True:
        T2 = (T.astype(np.float64) @ T.astype(np.float64)) > 0
        if np.array_equal(T2, T):
            return T
        T = T2


def swap_table(cats: np.ndarray, oh: np.ndarray,
               X) -> tuple[np.ndarray, np.ndarray]:
    """For an independent X (row ids, in order) and ``oh = onehot(cats)``:
    (ok bool[m, len(X)], extend bool[m]). ok[v, j]: X - X[j] + v is
    independent; extend[v]: X + v is. Rows of X themselves read garbage
    (callers skip them)."""
    X = [int(r) for r in X]
    owner = match(cats, X)
    if len(owner) != len(X):
        raise ValueError("swap_table needs an independent set")
    h = oh.shape[1]
    at = {r: j for j, r in enumerate(X)}
    lab_of = np.empty(len(X), np.int64)  # the label M gives X[j]
    step = np.zeros((h, h), bool)  # c -> labels of the row matched to c
    for c, r in owner.items():
        lab_of[at[r]] = c
        step[c] = oh[r]
    free = np.ones(h, bool)
    free[list(owner)] = False
    reach = (oh.astype(np.float64) @ _closure(step).astype(np.float64)) > 0
    extend = (reach & free).any(axis=1)
    ok = extend[:, None] | reach[:, lab_of]
    return ok, extend


def swap_outs(cats: np.ndarray, h: int, X, v: int) -> list[int]:
    """The rows u of X such that X - u + v is independent."""
    ok, _ = swap_table(cats, onehot(cats, h), list(X))
    return [int(u) for u, good in zip(X, ok[v]) if good]


def solve(D: np.ndarray, cats: np.ndarray, h: int, k: int,
          allow: np.ndarray, *, max_sweeps: int = 64) -> list[int]:
    """Selected rows of D, in the solver's order."""
    m = D.shape[0]
    oh = onehot(cats, h)
    chosen: list[int] = []
    inside = np.zeros(m, bool)
    gain = D.sum(axis=1)
    while len(chosen) < k:
        _ok, extend = swap_table(cats, oh, chosen)
        can = allow & ~inside & extend
        if not can.any():
            break
        v = int(np.argmax(np.where(can, gain, -np.inf)))
        chosen.append(v)
        inside[v] = True
        gain = D[:, chosen].sum(axis=1)
    X = chosen
    if len(X) < k:
        return X
    div = D[np.ix_(X, X)].sum() / 2.0
    for _ in range(max_sweeps):
        improved = False
        row = D[X][:, X].sum(axis=1)  # row[j]: distances of X[j] to X
        ok = None
        for v in np.flatnonzero(allow):
            if inside[v]:
                continue
            Xa = np.asarray(X)
            new = div - row + D[v, Xa].sum() - D[Xa, v]
            better = new > div
            if not better.any():
                continue
            if ok is None:
                ok, _ = swap_table(cats, oh, X)
            hit = np.flatnonzero(better & ok[v])
            if hit.size == 0:
                continue
            j = int(hit[0])
            u = X[j]
            X = X[:j] + X[j + 1:] + [int(v)]
            inside[u], inside[v] = False, True
            div = float(new[j])
            row = D[X][:, X].sum(axis=1)
            ok = None
            improved = True
        if not improved:
            break
    return X
