"""Matroid axioms (hypothesis property tests) + oracle cross-checks."""
import itertools

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)"
)
from hypothesis import given, settings, strategies as st

from repro.core.matroid import (
    MatroidSpec,
    PartitionMatroid,
    TransversalMatroid,
    UniformMatroid,
    partition_extract_mask,
    rank_in_group,
    transversal_extract_mask,
)

import jax.numpy as jnp


# --------------------------------------------------------------------------
# instance generators
# --------------------------------------------------------------------------

partition_instances = st.tuples(
    st.integers(4, 14),  # n
    st.integers(2, 4),  # h
    st.integers(1, 3),  # cap
    st.randoms(use_true_random=False),
)

transversal_instances = st.tuples(
    st.integers(4, 12),  # n
    st.integers(2, 5),  # h
    st.integers(1, 2),  # gamma
    st.randoms(use_true_random=False),
)


def _mk_partition(n, h, cap, rnd):
    cats = np.array([rnd.randrange(h) for _ in range(n)], np.int32)
    caps = np.full(h, cap, np.int32)
    return PartitionMatroid(cats, caps)


def _mk_transversal(n, h, gamma, rnd):
    cats = np.full((n, gamma), -1, np.int32)
    for i in range(n):
        k = rnd.randrange(1, gamma + 1)
        cs = rnd.sample(range(h), k)
        cats[i, : len(cs)] = cs
    return TransversalMatroid(cats, h)


def _check_axioms(m, n, rnd, trials=40):
    # hereditary: subsets of independent sets are independent
    for _ in range(trials):
        size = rnd.randrange(1, min(n, 6) + 1)
        s = rnd.sample(range(n), size)
        if m.is_independent(s):
            for r in range(len(s)):
                sub = s[:r] + s[r + 1:]
                assert m.is_independent(sub), (s, sub)
    # augmentation: |A| > |B| both independent => exists x in A\B extending B
    for _ in range(trials):
        a = rnd.sample(range(n), min(n, rnd.randrange(2, 6)))
        b = rnd.sample(range(n), rnd.randrange(1, len(a)))
        a = m.greedy_independent(a, len(a))
        b = m.greedy_independent(b, len(b))
        if len(a) > len(b):
            assert any(
                m.is_independent(b + [x]) for x in a if x not in b
            ), (a, b)


@settings(max_examples=25, deadline=None)
@given(partition_instances)
def test_partition_axioms(inst):
    n, h, cap, rnd = inst
    _check_axioms(_mk_partition(n, h, cap, rnd), n, rnd)


@settings(max_examples=25, deadline=None)
@given(transversal_instances)
def test_transversal_axioms(inst):
    n, h, gamma, rnd = inst
    _check_axioms(_mk_transversal(n, h, gamma, rnd), n, rnd)


@settings(max_examples=20, deadline=None)
@given(transversal_instances)
def test_transversal_matching_vs_bruteforce(inst):
    """Kuhn maximum matching == brute-force max independent subset size."""
    n, h, gamma, rnd = inst
    m = _mk_transversal(n, h, gamma, rnd)
    idxs = list(range(min(n, 8)))

    def brute_max():
        best = 0
        for r in range(len(idxs), 0, -1):
            for comb in itertools.combinations(idxs, r):
                # check perfect matching by brute force over category choices
                def ok(rem, used):
                    if not rem:
                        return True
                    x = rem[0]
                    for c in m.cats[x]:
                        if c >= 0 and c not in used:
                            if ok(rem[1:], used | {int(c)}):
                                return True
                    return False

                if ok(list(comb), set()):
                    return r
        return 0

    assert m.max_matching(idxs) == brute_max()


@settings(max_examples=20, deadline=None)
@given(transversal_instances)
def test_greedy_independent_is_maximum(inst):
    n, h, gamma, rnd = inst
    m = _mk_transversal(n, h, gamma, rnd)
    full = m.greedy_independent(list(range(n)), n)
    assert len(full) == m.max_matching(range(n))
    assert m.is_independent(full)


# --------------------------------------------------------------------------
# jit-side vectorized helpers
# --------------------------------------------------------------------------


def test_rank_in_group():
    g = jnp.array([0, 1, 0, 0, 1, 2], jnp.int32)
    v = jnp.array([1, 1, 1, 0, 1, 1], bool)
    r = rank_in_group(g, v, 3)
    assert list(np.asarray(r)[[0, 1, 2, 4, 5]]) == [0, 0, 1, 1, 0]
    assert int(r[3]) > 100  # invalid parked


@settings(max_examples=20, deadline=None)
@given(partition_instances, st.integers(1, 4), st.integers(1, 3))
def test_partition_extract_matches_host_greedy(inst, k, tau):
    """The vectorized Thm-1 EXTRACT picks, per cluster, an independent set of
    the size the host greedy achieves (largest <= k)."""
    n, h, cap, rnd = inst
    m = _mk_partition(n, h, cap, rnd)
    assign = np.array([rnd.randrange(tau) for _ in range(n)], np.int32)
    mask = np.asarray(partition_extract_mask(
        jnp.asarray(assign), jnp.asarray(m.cats[:, None]),
        jnp.asarray(m.caps, jnp.int32), jnp.ones((n,), bool), k, tau, h,
    ))
    for c in range(tau):
        members = np.flatnonzero(assign == c)
        sel = [i for i in members if mask[i]]
        assert m.is_independent(sel)
        want = len(m.greedy_independent(list(members), k))
        assert len(sel) == want, (c, sel, want)


@settings(max_examples=20, deadline=None)
@given(transversal_instances, st.integers(1, 3), st.integers(1, 3))
def test_transversal_extract_covers_categories(inst, k, tau):
    """The matching-free rule keeps min(k, |A ∩ C|) points of every category
    present in every cluster (the sufficient condition of DESIGN.md §8.4)."""
    n, h, gamma, rnd = inst
    m = _mk_transversal(n, h, gamma, rnd)
    assign = np.array([rnd.randrange(tau) for _ in range(n)], np.int32)
    mask = np.asarray(transversal_extract_mask(
        jnp.asarray(assign), jnp.asarray(m.cats),
        jnp.ones((n,), bool), k, tau, h,
    ))
    for c in range(tau):
        members = np.flatnonzero(assign == c)
        for a in range(h):
            in_cat = [i for i in members if a in set(m.cats[i])]
            kept = [i for i in in_cat if mask[i]]
            assert len(kept) >= min(k, len(in_cat)), (c, a, kept, in_cat)


def test_uniform_matroid():
    m = UniformMatroid(10, 3)
    assert m.is_independent([0, 1, 2])
    assert not m.is_independent([0, 1, 2, 3])
    assert not m.is_independent([0, 0, 1])


def _greedy_matching_loop(cats, valid, h):
    """The sequential first-free-category loop over slot order (the
    reference ``greedy_matching_slots`` must reproduce)."""
    used = np.zeros(h, bool)
    matched = np.zeros(len(valid), bool)
    for s in np.flatnonzero(valid):
        for c in cats[s]:
            if c >= 0 and not used[c]:
                used[c] = matched[s] = True
                break
    return used, matched


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("slots,h", [(64, 5), (512, 100)])
def test_greedy_matching_slots_equals_sequential_loop(seed, slots, h):
    from repro.core.solvers.matching import greedy_matching_slots

    rng = np.random.default_rng(seed)
    cats = np.full((slots, 3), -1, np.int32)
    # few topics per region (long displacement chains) on even seeds
    cats[:, 0] = rng.integers(0, h if seed % 2 else max(2, h // 8), slots)
    extra = rng.random((slots, 2)) < (0.4, 0.1)
    cats[:, 1:] = np.where(extra, rng.integers(0, h, (slots, 2)), -1)
    valid = rng.random(slots) < rng.uniform(0.05, 0.9)
    used, matched = greedy_matching_slots(
        jnp.asarray(cats), jnp.asarray(valid), h
    )
    want_used, want_matched = _greedy_matching_loop(cats, valid, h)
    np.testing.assert_array_equal(np.asarray(used), want_used)
    np.testing.assert_array_equal(np.asarray(matched), want_matched)
