"""The plain transversal reference of the benchmark
(``bench/reference/transversal.py``) and the program under it, at small
size on the CPU:

* the reference's independence oracle, rank and swap table agree with
  brute-force enumeration of matchings on tiny instances;
* a service restored from a checkpoint of the float64 Alg. 2 reference
  (``bench/reference/alg2.py``) over multi-label rows answers, through
  ``engine="jit_sum"`` and ``engine="host"``, with independent sets of size
  min(k, rank of the allowed rows) that no independence-keeping swap
  improves in float64 (``host`` exactly; ``jit_sum`` within the limit the
  benchmark's transversal cell sets, since float32 rounding may leave
  another sound local optimum);
* a transversal stream (no caps, three labels a row) restores bit for bit
  under every placement drive.
"""
import importlib.util
import itertools
import json
import math
import os

import numpy as np
import pytest

from conftest import make_clustered_points
from repro.core.matroid import MatroidSpec
from repro.serve.diversity import (
    DiversityQuery, DiversityService, StreamRuntime)
from test_checkpoint_restore import PLACEMENTS, _assert_state_equal, _batches

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _load(rel):
    spec = importlib.util.spec_from_file_location(
        "bench_" + rel.replace("/", "_")[:-3], os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tv = _load("reference/transversal.py")
alg2 = _load("reference/alg2.py")
with open(os.path.join(BENCH, "checks", "wiki-restored-query.json")) as f:
    SWAP_GAIN_LIMIT = json.load(f)["swap_gain"]


def _labels(rng, n, h, gamma=3, p_more=(0.5, 0.3)):
    """-1 padded label lists: a first label, then each further one with
    its probability, distinct within a row."""
    cats = np.full((n, gamma), -1, np.int32)
    for i in range(n):
        want = 1 + sum(rng.random() < p for p in p_more[: gamma - 1])
        cats[i, :want] = rng.choice(h, want, replace=False)
    return cats


# -- the oracle against enumeration ----------------------------------------


def _brute_independent(cats, rows):
    """Some choice of one label per row gives every row its own label."""
    lists = [[int(c) for c in cats[r] if c >= 0] for r in rows]
    return any(len(set(pick)) == len(pick)
               for pick in itertools.product(*lists))


def _brute_rank(cats, rows):
    return max(len(s) for r in range(len(rows) + 1)
               for s in itertools.combinations(rows, r)
               if _brute_independent(cats, s))


@pytest.mark.parametrize("seed", range(6))
def test_oracle_and_rank_match_enumeration(seed):
    rng = np.random.default_rng(seed)
    m, h = 7, 5
    cats = _labels(rng, m, h, p_more=(0.3, 0.2))
    rows = list(range(m))
    for r in range(m + 1):
        for s in itertools.combinations(rows, r):
            assert tv.independent(cats, s) == _brute_independent(cats, s), s
    assert tv.rank(cats, rows) == _brute_rank(cats, rows)
    for sub in ([0, 1, 2], [3, 4, 5, 6], [1, 3, 5]):
        assert tv.rank(cats, sub) == _brute_rank(cats, sub)


@pytest.mark.parametrize("seed", range(6))
def test_swap_table_matches_enumeration(seed):
    rng = np.random.default_rng(100 + seed)
    m, h = 9, 5
    cats = _labels(rng, m, h, p_more=(0.3, 0.2))
    oh = tv.onehot(cats, h)
    checked = 0
    for r in range(1, 5):
        for X in itertools.combinations(range(m), r):
            if not _brute_independent(cats, X):
                continue
            ok, extend = tv.swap_table(cats, oh, X)
            for v in set(range(m)) - set(X):
                assert extend[v] == _brute_independent(cats, X + (v,))
                for j, u in enumerate(X):
                    rest = tuple(x for x in X if x != u) + (v,)
                    assert ok[v, j] == _brute_independent(cats, rest)
                assert tv.swap_outs(cats, h, X, v) == [
                    u for j, u in enumerate(X) if ok[v, j]]
                checked += 1
    assert checked > 0


@pytest.mark.parametrize("seed", range(3))
def test_reference_search_is_a_swap_optimal_basis(seed):
    rng = np.random.default_rng(200 + seed)
    m, h, k = 40, 6, 5
    cats = _labels(rng, m, h)
    P = rng.normal(size=(m, 3))
    D = np.sqrt(((P[:, None] - P[None]) ** 2).sum(-1))
    allow = rng.random(m) < 0.7
    X = tv.solve(D, cats, h, k, allow)
    assert tv.independent(cats, X) and allow[X].all()
    assert len(X) == min(k, tv.rank(cats, np.flatnonzero(allow)))
    assert _swap_gain(D, cats, h, X, allow) == 0.0


# -- the program restored from the reference scan --------------------------


def _swap_gain(D, cats, h, X, allow):
    """Largest relative gain of one independence-keeping swap (float64)."""
    X = np.asarray(X, np.int64)
    row = D[np.ix_(X, X)].sum(axis=1)
    div = row.sum() / 2.0
    new = div - row[None, :] + D[:, X].sum(axis=1)[:, None] - D[:, X]
    ok, _ = tv.swap_table(cats, tv.onehot(cats, h), X)
    out = allow.copy()
    out[X] = False
    ok &= out[:, None]
    if not ok.any():
        return 0.0
    return float(max(0.0, (new[ok] - div).max()) / div)


H, GAMMA, K, TAU, SHARDS, BATCH = 12, 3, 8, 32, 2, 50


@pytest.fixture(scope="module")
def restored(tmp_path_factory):
    """A service restored from the reference's checkpoint, and the
    reference coreset (src, float64 rows, labels) in buffer order."""
    from repro.core.streaming import StreamState
    from repro.serve.diversity.checkpoint import (
        checkpoint_path, save_checkpoint)

    rng = np.random.default_rng(7)
    n = 1600
    P = make_clustered_points(rng, n=n, d=6, centers=60, spread=0.5)
    cats = _labels(rng, n, H)
    cfg = {"d": 6, "matroid": {"kind": "transversal", "num_categories": H,
                               "gamma": GAMMA},
           "k": K, "tau": TAU, "slot_cap": 256}
    shards = [alg2.new_shard(cfg, None) for _ in range(SHARDS)]
    for j in range(n // BATCH):
        sh = shards[j % SHARDS]
        for i in range(j * BATCH, (j + 1) * BATCH):
            sh.push(alg2.normalize(P[i:i + 1], "euclidean")[0], cats[i], i)
    assert all(s.overflow == 0 for s in shards)
    live = [s.dv & s.cvalid[:, None] for s in shards]
    src = np.concatenate([s.ds[lv] for s, lv in zip(shards, live)])
    rows = np.concatenate([s.dp[lv] for s, lv in zip(shards, live)])
    labels = np.concatenate([s.dc[lv] for s, lv in zip(shards, live)])
    states = [StreamState(
        R=np.float32(s.R), x1=np.asarray(s.x1, np.float32),
        n_seen=np.int32(s.n_seen), centers=s.centers.astype(np.float32),
        cvalid=s.cvalid.copy(), dp=s.dp.astype(np.float32),
        dc=s.dc.astype(np.int32), dv=s.dv.copy(), ds=s.ds.astype(np.int32),
        overflow=np.int32(s.overflow)) for s in shards]
    meta = {
        "version": 1, "kind": "list", "wal_seq": -1, "next_seq": 0,
        "n_offered": n, "rr": math.ceil(n / BATCH) % SHARDS, "epoch": 0,
        "fingerprint": None, "poisoned_seqs": [],
        "config": {"spec": cfg["matroid"], "k": K, "tau": TAU,
                   "metric": "euclidean", "caps": None, "slot_cap": 256,
                   "variant": "radius", "num_shards": SHARDS,
                   "block_size": 32, "placement": "pipeline"},
    }
    d = str(tmp_path_factory.mktemp("tv-ckpt"))
    save_checkpoint(checkpoint_path(d, n, None), states, meta)
    svc = DiversityService.from_runtime(StreamRuntime.restore(d))
    yield svc, src, rows, labels
    svc.close()


def test_restored_epoch_is_the_reference_coreset(restored):
    svc, src, _rows, labels = restored
    snap = svc.runtime.latest()
    assert 200 <= src.size <= 1000
    assert np.array_equal(np.asarray(snap.src_idx), src)
    assert np.array_equal(np.asarray(snap.cats), labels)


@pytest.mark.parametrize("engine", ["jit_sum", "host"])
@pytest.mark.parametrize("k,n_allowed", [(3, None), (8, None), (5, 4),
                                         (8, 6), (6, 2)])
def test_answers_are_swap_optimal_bases(restored, engine, k, n_allowed):
    svc, src, rows, labels = restored
    rng = np.random.default_rng(k * 31 + (n_allowed or 0))
    allowed = (None if n_allowed is None else
               frozenset(int(c) for c in rng.choice(H, n_allowed, False)))
    r = svc.query(DiversityQuery(k=k, allowed_cats=allowed), engine=engine)
    assert r.engine == ("jit_sum" if engine == "jit_sum"
                        else "host_local_search")
    pos = {int(s): i for i, s in enumerate(src)}
    X = [pos[int(s)] for s in r.indices]
    allow = np.ones(src.size, bool) if allowed is None else (
        np.isin(labels, list(allowed)) & (labels >= 0)).any(axis=1)
    assert len(set(X)) == len(X) and allow[X].all()
    assert tv.independent(labels, X)
    assert len(X) == min(k, tv.rank(labels, np.flatnonzero(allow)))
    D = np.sqrt(np.maximum(((rows[:, None] - rows[None]) ** 2).sum(-1), 0))
    gain = _swap_gain(D, labels, H, X, allow) if len(X) == k else 0.0
    if engine == "host":
        assert gain == 0.0
    else:
        assert gain <= SWAP_GAIN_LIMIT


# -- restore of a transversal stream ---------------------------------------


@pytest.mark.parametrize("placement,num_shards", PLACEMENTS)
def test_transversal_restore_is_bit_identical_across_placements(
    rng, tmp_path, placement, num_shards
):
    """A durable transversal stream (caps None, three labels a row),
    checkpointed mid-stream and abandoned without close(), restores to the
    live stream and to an uninterrupted synchronous run, bit for bit."""
    P = make_clustered_points(rng, n=400)
    cats = _labels(rng, 400, 6)
    spec = MatroidSpec("transversal", num_categories=6, gamma=3)
    batches = _batches(P, cats, 50)
    kw = dict(tau=12, block_size=32, num_shards=num_shards,
              placement=placement)
    rt = StreamRuntime(spec, 4, durability=str(tmp_path), **kw)
    half = len(batches) // 2
    for pts, cs in batches[:half]:
        rt.submit(pts, cs)
    rt.flush()
    assert rt.checkpoint(force=True) is not None
    for pts, cs in batches[half:]:
        rt.submit(pts, cs)
    rt.flush()
    live = rt.latest()
    restored = StreamRuntime.restore(str(tmp_path))
    assert restored.caps is None
    assert restored.restore_report["replayed_batches"] == len(batches) - half
    got = restored.latest()
    assert got.fingerprint == live.fingerprint
    assert np.array_equal(got.cats, live.cats)
    assert np.asarray(got.cats).shape[1] == 3
    assert np.array_equal(got.src_idx, live.src_idx)
    _assert_state_equal(restored.state, rt.state)
    ref = StreamRuntime(spec, 4, **kw)
    for pts, cs in batches:
        ref.ingest(pts, cs)
    assert ref.refresh(force=True).fingerprint == got.fingerprint
    _assert_state_equal(restored.state, ref.state)
    restored.close()
    ref.close()
    rt.close()
