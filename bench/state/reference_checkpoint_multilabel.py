"""Set-up: the service starts from a checkpoint of the plain Alg. 2 scan,
for a matroid whose rows carry several labels (transversal).

As ``reference_checkpoint``: the catalog (``points`` rows of the
configuration's generator in batches of ``batch``, batch j to shard state
j mod shards) is scanned by the float64 reference
(``bench/reference/alg2.py``), one spawned numpy process per shard state,
written in the program's checkpoint format through its own
``save_checkpoint`` (the shard layout from
``reference_checkpoint.program_arrays``), and the runtime is rebuilt by
``StreamRuntime.restore``. What differs: the ``Catalog`` keeps each
coreset row's full label list (``cats``: int64[m, gamma], -1 padded) and
``caps`` None, and the checkpoint's meta states the transversal spec with
no caps. A shard whose ``overflow`` counter is not 0 dropped a delegate
Alg. 2 would keep (its ``slot_cap`` is below Alg. 2's bound): set-up
refuses such a state.

The mix's ``state`` entry: ``{"how": "reference_checkpoint_multilabel",
"points": n, "batch": b}``.
"""
from __future__ import annotations

import math
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from harness import Refused, load_module
from reference import alg2

base = load_module("state", "reference_checkpoint")


def reference_catalog(cfg: dict, p: dict, seed: int):
    """(shards, Catalog) of the reference scan; Refused on an overflow."""
    n, b = int(p["points"]), int(p["batch"])
    gen = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "data", cfg["generator"] + ".py")
    with ProcessPoolExecutor(
            cfg["shards"], mp_context=multiprocessing.get_context("spawn")
    ) as pool:
        shards = list(pool.map(alg2.scan_shard, *zip(*[
            (gen, cfg, seed, n, b, i) for i in range(cfg["shards"])])))
    over = [int(s.overflow) for s in shards]
    if any(over):
        raise Refused(f"slot_cap {cfg['slot_cap']} overflowed in the "
                      f"reference scan (overflow per shard {over})")
    live = [s.dv & s.cvalid[:, None] for s in shards]
    cat = base.Catalog(
        src=np.concatenate([s.ds[lv] for s, lv in zip(shards, live)]),
        rows=np.concatenate([s.dp[lv] for s, lv in zip(shards, live)]),
        cats=np.concatenate([s.dc[lv] for s, lv in zip(shards, live)]),
        caps=None,
        points=n,
    )
    return shards, cat


def build(cfg: dict, p: dict, seed: int, registry, workdir: str):
    """Returns (service, Catalog)."""
    from repro.core.streaming import StreamState
    from repro.serve.diversity import DiversityService
    from repro.serve.diversity.checkpoint import (
        checkpoint_path, save_checkpoint)
    from repro.serve.diversity.runtime import StreamRuntime

    shards, cat = reference_catalog(cfg, p, seed)
    mat = cfg["matroid"]
    meta = {
        "version": 1, "kind": "list", "wal_seq": -1, "next_seq": 0,
        "n_offered": cat.points,
        "rr": math.ceil(cat.points / int(p["batch"])) % cfg["shards"],
        "epoch": 0, "fingerprint": None, "poisoned_seqs": [],
        "config": {
            "spec": {"kind": mat["kind"],
                     "num_categories": mat["num_categories"],
                     "gamma": mat["gamma"]},
            "k": cfg["k"], "tau": cfg["tau"], "metric": cfg["metric"],
            "caps": None, "slot_cap": cfg["slot_cap"],
            "variant": "radius", "num_shards": cfg["shards"],
            "block_size": cfg["block_size"], "placement": cfg["placement"],
        },
    }
    states = [StreamState(**base.program_arrays(s)) for s in shards]
    save_checkpoint(checkpoint_path(workdir, cat.points, None), states, meta)
    del states, shards
    rt = StreamRuntime.restore(workdir, registry=registry)
    if rt.placement != cfg["placement"]:
        rt.close()
        raise RuntimeError(f"placement resolved to {rt.placement!r}, the "
                           f"configuration states {cfg['placement']!r}")
    return DiversityService.from_runtime(rt, registry=registry), cat
