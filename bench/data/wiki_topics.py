"""Seeded stream shaped like the paper's Wikipedia testbed.

Source: arXiv:2002.03175, Table 2: about 5.9M Wikipedia pages embedded in
d=25 under the cosine distance, a transversal matroid over h=100 topics in
which a page carries up to gamma=3 topics, rank 100.

No pages ship with this repository, so the rows are drawn with the
geometry of ``benchmarks/common.py:wikipedia_like`` (copied here, not
imported):

* each topic a point of a 4-d manifold embedded linearly in 25-d, and a
  page its first topic's point plus N(0, NOISE^2) noise per coordinate;
* topic shares from Dirichlet(SHARE_ALPHA) over the 100 topics: skewed,
  as Wikipedia's are (0.25% to 2.1% at world_seed 0). The Dirichlet(0.5)
  of ``songs_bow``'s genres leaves topics with shares near 1e-6, which a
  center never sees k times: Alg. 2 then keeps every page that carries one,
  and the reference scan overflowed its 2,048 delegate slots within a
  shard's first 8,192 pages;
* a page's first topic drawn from the shares, then a second with
  probability P_SECOND and a third with probability P_THIRD, each from the
  same shares and distinct from the topics before it. The label list keeps
  that order, -1 padded to gamma.

Those laws are assumed (see the configuration's ``assumed``). A
transversal matroid has no caps: ``caps`` is None.

The corpus is the deployment's: the shares, the manifold and every page
come from the configuration's ``world_seed`` (batch ``b`` from
``(world_seed, b)``, so any batch can be made on its own). The run's seed
permutes the 25 coordinates and renames the 100 topics, keeping the order
of each page's list. Both are isometries of the transversal instance under
the cosine distance: every distance, every greedy matching of Alg. 2's
shrink step and so the coreset and its size m are the deployment's, while
every row and label the service reads differ from seed to seed.
"""
from __future__ import annotations

import numpy as np

H = 100
GAMMA = 3
MANIFOLD = 4
NOISE = 0.6
SHARE_ALPHA = 5.0
P_SECOND = 0.4
P_THIRD = 0.1


class Stream:
    def __init__(self, cfg: dict, seed: int):
        mat = cfg["matroid"]
        if (mat["kind"] != "transversal" or mat["num_categories"] != H
                or mat["gamma"] != GAMMA):
            raise ValueError("wiki_topics makes a transversal matroid over "
                             "100 topics, 3 per page")
        self.d = int(cfg["d"])
        self.world = int(cfg["world_seed"])
        rng = np.random.default_rng([self.world, 0])
        self.shares = rng.dirichlet(np.ones(H) * SHARE_ALPHA)
        basis = rng.normal(size=(MANIFOLD, self.d))
        self.topic_at = rng.normal(size=(H, MANIFOLD)) @ basis  # (H, d)
        self.caps = None
        ren = np.random.default_rng([int(seed) % (1 << 64), 2])
        self.coords = ren.permutation(self.d)  # column j holds coords[j]
        self.topics = ren.permutation(H)  # topic t is labelled topics[t]

    def _distinct(self, rng, taken: np.ndarray) -> np.ndarray:
        """One topic per row from the shares, none of the row's ``taken``."""
        got = rng.choice(H, taken.shape[0], p=self.shares)
        while True:
            clash = np.flatnonzero((got[:, None] == taken).any(axis=1))
            if clash.size == 0:
                return got
            got[clash] = rng.choice(H, clash.size, p=self.shares)

    def batch(self, b: int, n: int) -> tuple[np.ndarray, np.ndarray]:
        """Rows f32[n, d] and topic lists int32[n, 3] (-1 padded) of
        batch b."""
        rng = np.random.default_rng([self.world, 1, int(b)])
        first = rng.choice(H, n, p=self.shares)
        P = self.topic_at[first] + NOISE * rng.normal(size=(n, self.d))
        labels = np.full((n, GAMMA), -1, np.int64)
        labels[:, 0] = first
        second = rng.random(n) < P_SECOND
        third = rng.random(n) < P_THIRD
        labels[second, 1] = self._distinct(rng, labels[second, :1])
        # the third topic follows whatever the page holds already
        slot = np.where(second, 2, 1)
        rows = np.flatnonzero(third)
        labels[rows, slot[rows]] = self._distinct(rng, labels[rows, :2])
        named = np.where(labels >= 0, self.topics[np.maximum(labels, 0)], -1)
        return (P[:, self.coords].astype(np.float32),
                named.astype(np.int32))
