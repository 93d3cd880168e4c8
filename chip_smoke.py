#!/usr/bin/env python3
"""End-to-end smoke run of the diversity service on a TPU.

    python3 chip_smoke.py              # one chip: ingest, queries, parity
    python3 chip_smoke.py --chips 4    # shard_map over 4 chips vs vmap on 1

The deployment is the paper's Wikipedia testbed at its published shape
(Table 2): 25-d GloVe-like vectors under the cosine metric, a transversal
matroid over 100 topics with up to 3 topics per page, rank k=100. Points come
from ``benchmarks.common.wikipedia_like`` with ``--seed``; the stream is cut
from the published 5.9M pages to ``--n``.

The served stream runs over 4 shard states, each its own
Alg. 2 scan, so the union coreset the queries read holds thousands of
points: under the tau-controlled radius variant the first two points of a
scan fix its radius, and one scan of this stream keeps 4-5 centers of k=100
delegates whatever tau is. The scan is per point (``block_size=1``): a
saturated transversal center adds, then shrinks, almost every point it
sees, so a block precheck would find nearly every point active. Each center
holds ``SLOT_CAP`` delegate slots instead of Alg. 2's bound gamma*k^2 =
30000; while no delegate is dropped (checked) the coreset is the one the
full bound gives, from a state 15 times smaller.

One chip runs the served path through its public entry points:
``DiversityService`` + ``warmup()``, async ``runtime.submit`` of the stream,
``frontend.flush()``, then one heterogeneous ``query_batch`` with
``engine="auto"``. It then checks, on the chip:

* every ``auto`` answer whose host solve is affordable (k <= HOST_KMAX)
  equals ``engine="host"`` on the same epoch (index set and diversity), and
  at least one answer came from the device engine ``jit_sum``;
* the blocked scan with the Pallas precheck ends in the same state as the
  per-point ``step_impl="reference"`` scan on a prefix of the stream;
* the async worker recorded no error and quarantined no batch.

``--chips 4`` runs only the sharded phase: the same stream through
``DiversityService(num_shards=8, placement="auto")``, which resolves to the
``shard_map`` drive over the four chips, against ``placement="vmap"`` on one
chip, comparing fingerprints, scan states and query answers.

The last line of stdout is one JSON object naming the device; it is printed
only when every check passed. The script exits non-zero, with no such line,
when JAX finds no TPU or when ``REPRO_KERNEL_BACKEND`` would steer the kernels
off the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PUBLISHED_N = 5_900_000  # Wikipedia pages in the paper's Table 2
D, H, GAMMA, K = 25, 100, 3, 100
TAU = 32  # center budget per shard; the stream keeps 4-5 centers anyway
SHARDS = 4  # one-chip stream: m in the thousands; a k=100 solve grows with m^2
SHARDS_4 = 8  # the four-chip phase: 2 shard states per chip
PROGRESS = 16  # batches between progress lines (each one a flush)
SLOT_CAP = 2048  # delegate slots per center; Alg. 2's bound is 30000
BATCH = 16384  # points per submitted batch
PREFIX = 1 << 16  # stream prefix of the scan parity check
PARITY_BLOCK = 128  # blocked-scan block of the scan parity check
HOST_KMAX = 16  # largest k whose host solve is compared
OUT = os.path.join(ROOT, "chiprun_out")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="stream length (published: 5.9M)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def require_tpu(want: int):
    """The device JAX reports, or SmokeFailure: no fallback hides it."""
    forced = os.environ.get("REPRO_KERNEL_BACKEND")
    check(not forced,
          f"REPRO_KERNEL_BACKEND={forced!r} would steer the kernels off the "
          "chip; unset it")
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX reports platform {devs[0].platform!r}")
    check(len(devs) >= want, f"need {want} chips, JAX reports {len(devs)}")
    return devs


def queries():
    from repro.serve.diversity import DiversityQuery

    return [
        DiversityQuery(k=8),
        DiversityQuery(k=8, allowed_cats=frozenset(range(0, 50))),
        DiversityQuery(k=16),
        DiversityQuery(k=16, allowed_cats=frozenset(range(25, 75))),
        DiversityQuery(k=100),
        DiversityQuery(k=100, allowed_cats=frozenset(range(0, H, 2))),
    ]


def same_answer(a, b) -> bool:
    return (
        a.epoch == b.epoch
        and set(a.indices.tolist()) == set(b.indices.tolist())
        and a.diversity == b.diversity
    )


def make_service(args, registry, **kw):
    from repro.serve.diversity import DiversityService

    from benchmarks.common import wikipedia_like

    _P, _c, _caps, spec = wikipedia_like(1, seed=args.seed)
    return DiversityService(
        spec, K, tau=TAU, metric="cosine", registry=registry,
        slot_cap=SLOT_CAP, block_size=1, **kw,
    )


def stream(args, n):
    from benchmarks.common import wikipedia_like

    t0 = time.perf_counter()
    P, cats, _caps, spec = wikipedia_like(n, seed=args.seed)
    check(spec.kind == "transversal" and spec.num_categories == H
          and spec.gamma == GAMMA and P.shape[1] == D,
          f"generator shape drifted: {spec}, d={P.shape[1]}")
    say(phase="data", n=n, published_n=PUBLISHED_N,
        cut=f"{PUBLISHED_N / n:.2f}x", gen_s=f"{time.perf_counter() - t0:.3f}")
    return P, cats


def ingest(svc, P, cats, batch):
    """Async submit of the whole stream, then the freshness barrier."""
    import numpy as np

    rt = svc.runtime
    n = P.shape[0]
    t0 = time.perf_counter()
    for i in range(0, n, batch):
        rt.submit(P[i:i + batch], cats[i:i + batch])
        done = min(i + batch, n)
        if done == n or (i // batch + 1) % PROGRESS == 0:
            epoch = svc.frontend.flush(timeout=None)
            dt = time.perf_counter() - t0
            say(phase="ingest_progress", points=done,
                points_per_s=f"{done / dt:.1f}")
    reg = rt.registry
    errs = reg.counter("serve.worker.errors").value
    cb = reg.counter("serve.publish.callback_errors").value
    check(errs == 0, f"serve.worker.errors={errs}")
    check(cb == 0, f"serve.publish.callback_errors={cb}")
    check(not rt.poison, f"{len(rt.poison)} poisoned batches")
    check(rt.n_offered == P.shape[0],
          f"ingested {rt.n_offered} of {P.shape[0]} points")
    # a full delegate buffer drops points the paper's bound would keep
    states = rt.state if isinstance(rt.state, list) else [rt.state]
    dropped = sum(int(np.asarray(st.overflow).sum()) for st in states)
    check(dropped == 0, f"{dropped} delegates dropped at SLOT_CAP")
    return epoch, dt


def scan_parity(args, P, cats, spec, rec: dict) -> None:
    """The blocked scan with the Pallas precheck vs the per-point
    cond-ladder reference, on a prefix of the stream: equal states."""
    from repro.core import geometry
    from repro.core.streaming import (
        epoch_fingerprint, ingest_batch, init_stream_state, state_to_arrays,
    )

    import jax.numpy as jnp
    import numpy as np

    n0 = min(PREFIX, args.n)
    pts = geometry.normalize_for_metric(jnp.asarray(P[:n0]), "cosine")
    cj = jnp.asarray(cats[:n0])
    valid = jnp.ones((n0,), bool)
    states = {}
    for name, kw in (("blocked_pallas", dict(block_size=PARITY_BLOCK)),
                     ("reference", dict(block_size=1,
                                        step_impl="reference"))):
        t0 = time.perf_counter()
        st = ingest_batch(
            init_stream_state(D, GAMMA, spec, K, TAU, slot_cap=SLOT_CAP),
            pts, cj, valid, spec, None, K, TAU, **kw,
        )
        fp, size = epoch_fingerprint(st)
        states[name] = state_to_arrays(st)
        say(phase="scan_parity", impl=name, n=n0, fingerprint=fp,
            coreset_m=size, seconds=f"{time.perf_counter() - t0:.3f}")
        rec[f"scan_{name}"] = dict(fingerprint=fp, coreset_m=size)
    same = all(
        np.array_equal(states["blocked_pallas"][f], states["reference"][f])
        for f in states["reference"]
    )
    rec["scan_equal"] = same
    check(same, "blocked Pallas-precheck scan != reference scan")


def query_parity(svc, epoch, rec: dict) -> None:
    """One heterogeneous batch under ``engine="auto"``, then the host
    reference on every query whose host solve is affordable."""
    qs = queries()
    t0 = time.perf_counter()
    auto = svc.query_batch(qs, engine="auto")
    qa_s = time.perf_counter() - t0
    cmp = [i for i, q in enumerate(qs) if q.k <= HOST_KMAX]
    t0 = time.perf_counter()
    host = svc.query_batch([qs[i] for i in cmp], engine="host")
    qh_s = time.perf_counter() - t0
    say(phase="queries", batch=len(qs), auto_s=f"{qa_s:.3f}",
        host_s=f"{qh_s:.3f}", host_compared=len(cmp))
    rec.update(query_auto_s=qa_s, query_host_s=qh_s)
    rec["queries"] = []
    for i, (q, r) in enumerate(zip(qs, auto)):
        row = dict(k=q.k, filtered=q.allowed_cats is not None,
                   engine=r.engine, size=int(r.indices.size),
                   diversity=r.diversity, epoch=r.epoch,
                   coreset_m=r.coreset_size)
        if i in cmp:
            h = host[cmp.index(i)]
            row["host_engine"] = h.engine
            row["equal_host"] = same_answer(r, h)
        rec["queries"].append(row)
        say(phase="query", **row)
    for row in rec["queries"]:
        if "equal_host" in row:
            check(row["equal_host"], f"auto != host: {row}")
    check(any(r.engine == "jit_sum" for r in auto),
          "no query was answered by jit_sum")
    check(all(r.epoch == epoch for r in auto), "answers left the epoch")


def one_chip(args) -> dict:
    from repro import obs
    from repro.kernels import ops

    rec: dict = {}
    kpath = ops._mode(None)
    say(phase="kernels", path=kpath)
    check(kpath == "pallas", f"kernel path resolved to {kpath!r}")
    P, cats = stream(args, args.n)

    # 1. build + ahead-of-time compile of the ingest shape
    reg = obs.MetricsRegistry()
    t0 = time.perf_counter()
    svc = make_service(args, reg, num_shards=SHARDS, placement="pipeline")
    try:
        wu = svc.warmup(d=D, ingest_sizes=(min(BATCH, args.n),))
        compile_s = time.perf_counter() - t0
        say(phase="warmup", placement=svc.placement, shards=SHARDS,
            tau=TAU, slot_cap=SLOT_CAP, block=1,
            compile_s=f"{compile_s:.3f}", shapes=len(wu))
        rec["compile_s"] = compile_s

        # 2. async ingest of the stream
        epoch, dt = ingest(svc, P, cats, BATCH)
        m = svc.runtime.latest().size
        say(phase="ingest", n=args.n, seconds=f"{dt:.3f}",
            points_per_s=f"{args.n / dt:.1f}", epoch=epoch, coreset_m=m)
        rec.update(ingest_s=dt, points_per_s=args.n / dt, coreset_m=m,
                   epoch=epoch)
        check(m > 0, "empty coreset")

        # 3. scan parity on a prefix, then the queries
        scan_parity(args, P, cats, svc.spec, rec)
        query_parity(svc, epoch, rec)
    finally:
        svc.close()
    return rec


def four_chips(args) -> dict:
    from repro import obs

    import numpy as np

    from repro.core.streaming import state_to_arrays

    rec: dict = {}
    P, cats = stream(args, args.n)
    runs = {}
    for placement in ("auto", "vmap"):
        svc = make_service(args, obs.MetricsRegistry(), num_shards=SHARDS_4,
                           placement=placement)
        try:
            t0 = time.perf_counter()
            svc.warmup(d=D, ingest_sizes=(min(BATCH, args.n),))
            compile_s = time.perf_counter() - t0
            epoch, dt = ingest(svc, P, cats, BATCH)
            # the small-k queries: a short stream leaves every point in
            # the coreset, where a k=100 solve would dominate the phase
            qs = [q for q in queries() if q.k <= HOST_KMAX]
            ans = svc.query_batch(qs, engine="auto")
            st = state_to_arrays(svc.runtime.state)
            runs[placement] = (svc.placement, svc.runtime.fingerprint, ans, st)
            devices = sorted({
                d.id for a in svc.runtime.state for d in a.devices()
            })
            say(phase="sharded", requested=placement, placement=svc.placement,
                devices=devices, compile_s=f"{compile_s:.3f}",
                ingest_s=f"{dt:.3f}", points_per_s=f"{args.n / dt:.1f}",
                fingerprint=svc.runtime.fingerprint,
                coreset_m=svc.runtime.latest().size,
                engines=",".join(sorted({a.engine for a in ans})))
            rec[placement] = dict(placement=svc.placement, devices=devices,
                                  compile_s=compile_s, ingest_s=dt,
                                  fingerprint=svc.runtime.fingerprint)
        finally:
            svc.close()
    pl4, fp4, ans4, st4 = runs["auto"]
    pl1, fp1, ans1, st1 = runs["vmap"]
    check(pl4 == "shard_map", f"placement='auto' resolved to {pl4!r}")
    check(len(rec["auto"]["devices"]) == 4,
          f"shard_map state on devices {rec['auto']['devices']}")
    check(len(rec["vmap"]["devices"]) == 1,
          f"vmap state on devices {rec['vmap']['devices']}")
    check(fp4 == fp1, f"fingerprints differ: {fp4} vs {fp1}")
    check(all(np.array_equal(st4[f], st1[f]) for f in st1),
          "shard_map scan state != vmap scan state")
    for a, b in zip(ans4, ans1):
        check(set(a.indices.tolist()) == set(b.indices.tolist())
              and a.diversity == b.diversity,
              f"answers differ at k={a.indices.size}")
    rec["equal"] = True
    say(phase="sharded_parity", fingerprint_equal=True, state_equal=True,
        answers_equal=len(ans4))
    return rec


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        devs = require_tpu(args.chips)
        from repro.compile_cache import enable_compile_cache

        say(phase="setup", compile_cache=enable_compile_cache(),
            device_kind=devs[0].device_kind, device_count=len(devs))
        t0 = time.perf_counter()
        rec = (four_chips if args.chips == 4 else one_chip)(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    rec.update(wall_s=time.perf_counter() - t0, args=vars(args))
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"chip_smoke_{args.chips}.json"),
              "w") as f:
        json.dump(rec, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
