"""Serving benchmark: ingest throughput (blocked + sharded, per placement),
cold-vs-warmed query latency, batched QPS for the online diversity service.

    PYTHONPATH=src python -m benchmarks.serve_bench [--quick] [--json]
                                                    [--shards N]

``--json`` writes a ``BENCH_serve.json`` artifact (repo root) so the perf
trajectory is tracked across PRs; the artifact records the platform/device
and the block/shard configuration so trajectories are comparable across
machines. ``benchmarks.run --check`` reruns the quick configuration and
fails on >20% regressions of ``ingest_points_per_s`` / ``batched_qps`` /
``sharded_speedup`` against the committed artifact.

Ingest methodology: one long-lived service per configuration, all driven
through the same stream *interleaved* (both see the same host weather, so
their ratio is robust to scheduler noise), for ``WARM_ROUNDS`` full passes
(jit compiled, shard coresets saturated) plus measured continuation
rounds. Steady-state throughput is the best per-batch time of the measured
rounds — the only stable estimator of a single-digit-ms window on a noisy
shared host, and the honest serving number for a service at equilibrium
(the transient covers a vanishing fraction of an unbounded stream).
``sharded_speedup`` = sharded (auto placement) / unsharded steady-state
pps; per-placement numbers are recorded in ``ingest_pps_by_placement``.
``num_shards`` defaults to ``min(8, max(2, devices, cpus))`` — derived,
not hardcoded, so artifacts are comparable across machines — and
``--check`` reruns with the *committed* shard count.

Query latency: ``first_query_cold_s`` is the first query ever issued in
the process (pays trace+compile+pdist — the number ``warmup()`` exists to
absorb); ``first_query_warmed_s`` is the first query of a service that
called ``warmup()`` first; ``warmup_s`` is that warmup's wall time (in a
cold process it absorbs the full compile; here later warmups reuse the
process jit cache, which is exactly the serving story). "Cold" solve is
the full offline driver (``solve_dmmc``: rebuild coreset + pdist + solve).

Per solver-registry cell the bench records batched QPS
(``batched_qps_by_engine``) and the engine mix of representative auto
batches (``engine_mix``); ``--check`` additionally fails when a dispatch
regression routes transversal or star/tree batches back to 100% host.

Mixed workload (``mixed_workload``): the epoch-snapshot serving runtime
under contention — a background ``submit`` worker continuously ingesting
while the main thread queries published epochs (idle vs contended p50/p95
latency, ingest pps sustained during the query window) plus 4-tenant
cache fan-out from the single stream (per-tenant cached QPS vs the
single-tenant baseline). ``--check`` gates the two machine-relative
ratios everywhere: ``contention_p95_ratio <= 2.0`` and
``multi_tenant_min_ratio >= 0.8``.

Fault tolerance (``fault_tolerance``): crash-recovery wall time and WAL
replay throughput (durable stream killed without close, restored via
checkpoint + WAL-tail replay, parity asserted against the live
fingerprint), a seeded chaos ingest (worker crashes + transient errors +
a poisoned batch, stream must keep flowing), and a 4x-saturation
deadline burst (exact queries offered at 4x their measured capacity with
``deadline_s`` — every request must complete, degrade, or shed inside
the budget; misses are gated via the min-over-rounds methodology).
``--check`` gates ``replay_parity``, ``recovery_s <= 60``,
``replay_pps > 0``, ``stream_continued``, ``deadline_violations == 0``
and ``goodput >= 0.5``; the post-crash replay checkpoint + restore
report land in ``BENCH_fault_recovery/`` (CI uploads it).

Replication (``replication``): a ``ReplicaSet`` (primary + WAL-shipped
hot standby) driven through the stream with a seeded primary kill
planted mid-ingest — the write path promotes the standby inline
(replaying the acked WAL tail) and the run records ``failover_s``,
``failover_parity`` (post-failover fingerprint bit-identical to a
single-runtime replay — zero acked batches lost), per-batch replication
lag (the ``serve.replication.lag_batches`` histogram) and an
``IntegrityAuditor`` pass over the surviving set. ``--check`` gates
``failover_parity``, ``failover_s <= 5``, a populated lag histogram and
``audit_violations == 0``; the failover report lands in
``BENCH_failover/`` (CI uploads it).

Observability (``repro.obs``): every run embeds the full metrics snapshot
in the artifact (``metrics``), the recompile census keyed by compile
region (``recompiles_by_key``), the warmed-window recompile count
(``steady_state_recompiles`` — gated ``== 0`` by ``--check``: a measured
round that compiles anything is not steady state), the enabled-vs-disabled
registry cost (``obs_overhead`` — interleaved floors, target <= 3%), and
drops a Chrome ``trace_event`` artifact (``BENCH_serve.trace.json``, open
at chrome://tracing or ui.perfetto.dev) whose spans cover the full
submit -> worker_ingest -> publish -> query -> solve path.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform as _platform
import sys
import time

import numpy as np

from repro.compile_cache import enable_compile_cache

from .common import Timer, csv_line, songs_like, songs_multilabel

BLOCK_SIZE = 128
MAX_SHARDS = 8
INGEST_DUTY = 0.1  # mixed-workload stream arrival rate vs ingest capacity
WARM_ROUNDS = 2
MEASURE_ROUNDS = 3

_JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json",
)


def default_num_shards() -> int:
    """min(8, max(2, jax devices, host cpus)): enough shards to exercise
    the sharded drives everywhere, never more than the historical 8, and
    scaled to the machine instead of hardcoded (single-core runners got a
    meaningless 8-shard config before)."""
    import jax

    avail = max(jax.device_count(), os.cpu_count() or 1)
    return max(2, min(MAX_SHARDS, avail))


def _steady_ingest(
    factories: dict, P, cats, n: int, batch: int, steady_watch=None
) -> tuple[dict, dict]:
    """Interleaved steady-state ingest floors: returns
    ``({config: points/s}, {config: the service that produced it})``.

    Every service consumes the same stream; each round drives one full
    pass through *every* service before the next round starts, so all
    configs face the same host conditions and the recorded ratios are
    meaningful. The first WARM_ROUNDS passes compile and saturate (their
    times are discarded); the floor is min per-batch time afterwards.

    ``steady_watch`` (an ``obs.RecompileWatch``) is reset at the
    warm/measure boundary, so after return it holds exactly the XLA
    compiles triggered *inside* the measured rounds — the
    ``steady_state_recompiles == 0`` gate: a measured round that compiles
    anything is not measuring steady state (and the watch's by-key census
    names the bucketed shape that failed to hold).
    """
    svcs = {name: mk() for name, mk in factories.items()}
    best: dict = {name: [] for name in factories}
    for r in range(WARM_ROUNDS + MEASURE_ROUNDS):
        if r == WARM_ROUNDS and steady_watch is not None:
            steady_watch.reset()
        for off in range(0, n, batch):
            m = min(batch, n - off)
            # batch-granular interleave: every config ingests the same
            # batch back-to-back, so a host-noise burst hits all configs
            # rather than biasing whichever one it landed on
            for name, svc in svcs.items():
                with Timer() as t:
                    svc.ingest(P[off:off + m], cats[off:off + m])
                if r >= WARM_ROUNDS:
                    best[name].append(t.s / m)
    return (
        {name: 1.0 / float(np.min(v)) for name, v in best.items()},
        svcs,
    )


def _mixed_workload(P, cats, caps, spec, k: int, tau: int, quick: bool,
                    ingest_pps: float) -> dict:
    """Concurrent ingest + query section: one ``StreamRuntime`` ingesting
    asynchronously (background ``submit`` worker, epoch publication) while
    the main thread queries a ``QueryFrontend`` over it, plus >= 4-tenant
    cache fan-out from the single stream.

    The feeder offers the stream at ``INGEST_DUTY`` of the measured
    steady-state ingest throughput (recorded as ``ingest_target_pps``) —
    the serving scenario is a query service *with a live arrival rate*,
    not an offline bulk load. At 100% duty a host with two cores measures
    pure compute saturation (every XLA call wants every core), which says
    nothing about the architecture; at a real arrival rate the gate pins
    what the epoch-snapshot split is for: queries keep answering from
    published epochs while the scan runs, instead of blocking on device
    state behind it.

    Records p50/p95 warm query latency idle vs under active ingestion
    (``contention_p95_ratio`` — gated <= 2.0 by ``--check``: serving must
    not stall behind the scan), the ingest pps sustained *while* queries
    were answered, and per-tenant cached QPS (``multi_tenant_min_ratio``
    — gated >= 0.8: another tenant's entry must cost what the first one's
    does). Both gates are machine-relative ratios, enforced everywhere.
    """
    import threading

    from repro.core.matroid import MatroidSpec
    from repro.serve.diversity import (
        DiversityQuery,
        QueryFrontend,
        StreamRuntime,
    )

    n = P.shape[0]
    batch = 256  # smaller than bulk ingest: bounds per-call HOL blocking
    target_pps = INGEST_DUTY * ingest_pps
    rt = StreamRuntime(spec, k, tau=tau, caps=caps, block_size=BLOCK_SIZE)
    fe = QueryFrontend(rt)
    rt.ingest(P, cats)
    q = DiversityQuery(k=k)
    fe.query(q)  # build the default entry + compile the solver shape
    # pre-compile the contended ingest shape and the worker/publish path
    # so the measurement window sees steady state, not first-trace
    rt.ingest(P[:batch], cats[:batch])
    rt.submit(P[:batch], cats[:batch])
    rt.flush()

    def lat_run(m: int) -> np.ndarray:
        ls = np.empty(m)
        for i in range(m):
            t0 = time.perf_counter()
            fe.query(q)
            ls[i] = time.perf_counter() - t0
        return ls

    reps = 100 if quick else 250
    rounds = 4
    lat_run(reps // 4)  # saturate before measuring

    def feeder(stop):
        # re-stream the catalog at target_pps until the window closes
        interval = batch / target_pps
        off = 0
        next_t = time.perf_counter()
        while not stop.is_set():
            m = min(batch, n - off)
            try:
                rt.submit(P[off:off + m], cats[off:off + m])
            except RuntimeError:
                return
            off = (off + m) % n
            next_t += interval
            dt = next_t - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            else:  # fell behind (backpressure): don't burst to catch up
                next_t = time.perf_counter()

    # interleaved idle/contended rounds (the same methodology as the
    # ingest floors: both phases of a round share the host weather, and
    # the gated ratio is the min over rounds — one scheduler burst cannot
    # fail the gate, a real serving regression shifts every round)
    idle_all, cont_all, ratios, ingested, window = [], [], [], 0, 0.0
    for _ in range(rounds):
        idle = lat_run(reps)
        stop = threading.Event()
        th = threading.Thread(target=feeder, args=(stop,), daemon=True)
        offered0 = rt.n_offered
        th.start()
        t0 = time.perf_counter()
        contended = lat_run(reps)
        window += time.perf_counter() - t0
        ingested += rt.n_offered - offered0  # what the worker really took
        stop.set()
        th.join()
        rt.flush()
        idle_all.append(idle)
        cont_all.append(contended)
        ratios.append(
            float(np.percentile(contended, 95) / np.percentile(idle, 95))
        )
    idle = np.concatenate(idle_all)
    contended = np.concatenate(cont_all)

    # ---- multi-tenant fan-out: 4 keys, one stream, per-tenant QPS ----
    uspec = MatroidSpec("uniform")
    fe.register_tenant("cosine", metric="cosine")
    fe.register_tenant("uniform", spec=uspec)
    fe.register_tenant("uniform-cos", spec=uspec, metric="cosine")
    tenant_names = ["default", "cosine", "uniform", "uniform-cos"]
    qs = [DiversityQuery(k=2 + i % 7) for i in range(32)]

    for name in tenant_names:
        fe.query_batch(qs, tenant=name)  # build entries + warm the shape
    best = {name: np.inf for name in tenant_names}
    for _ in range(6):
        # tenant-interleaved rounds: every tenant measured back-to-back
        # under the same host weather, so the gated ratio (min tenant /
        # the default tenant, both best-of-rounds) compares cache fan-out
        # cost, not scheduler noise
        for name in tenant_names:
            with Timer() as t:
                got = fe.query_batch(qs, tenant=name)
            best[name] = min(best[name], t.s / len(got))
    per_tenant = {name: 1.0 / b for name, b in best.items()}
    single_tenant_qps = per_tenant["default"]
    min_ratio = min(per_tenant.values()) / single_tenant_qps
    stats = fe.stats()
    rt.close()
    idle_p95 = float(np.percentile(idle, 95))
    cont_p95 = float(np.percentile(contended, 95))
    return dict(
        idle_p50_s=float(np.percentile(idle, 50)),
        idle_p95_s=idle_p95,
        contended_p50_s=float(np.percentile(contended, 50)),
        contended_p95_s=cont_p95,
        contention_p95_ratio=float(np.min(ratios)),
        contention_p95_ratios=[float(x) for x in ratios],
        ingest_duty=float(INGEST_DUTY),
        ingest_target_pps=float(target_pps),
        contended_ingest_pps=float(ingested / window),
        query_reps=int(reps),
        tenant_count=len(tenant_names),
        single_tenant_qps=float(single_tenant_qps),
        tenant_qps={k_: float(v) for k_, v in per_tenant.items()},
        multi_tenant_min_ratio=float(min_ratio),
        epochs_published=int(stats["epochs_published"]),
        snapshot_materializations=int(stats["snapshot_materializations"]),
        cache=stats["cache"],
    )


def _fault_tolerance(P, cats, caps, spec, k: int, tau: int,
                     quick: bool) -> dict:
    """Fault-tolerance section: recovery, chaos ingest, deadline burst.

    *Recovery*: a durable stream (WAL + cadence checkpoints) is killed
    without ``close()`` and rebuilt with ``StreamRuntime.restore`` —
    recorded are the recovery wall time, the WAL-tail replay throughput,
    and ``replay_parity`` (restored fingerprint == the dead runtime's).
    The newest checkpoint plus the restore report are copied to
    ``BENCH_fault_recovery/`` so CI preserves the post-crash state.

    *Chaos*: a seeded ``FaultPlan`` injects worker crashes (supervisor
    restarts), transient ingest errors (retried away) and one
    twice-failing batch (quarantined); ``stream_continued`` asserts the
    stream kept flowing and lost exactly the poisoned points.

    *Deadline*: exact star/tree queries offered with a per-batch
    ``deadline_s`` of 1/4 their measured exact wall — a 4x-saturation
    burst. The admission layer must degrade (or shed) every batch into
    the budget; ``deadline_violations`` is the min over rounds of
    per-round deadline misses (one scheduler burst cannot fail the gate,
    unbounded queuing misses in every round) and ``goodput`` is the
    answered (non-shed) fraction.
    """
    import shutil
    import tempfile

    from repro import obs
    from repro.serve.diversity import (
        DiversityQuery,
        DurabilityConfig,
        FaultPlan,
        FaultPolicy,
        FaultRule,
        QueryFrontend,
        StreamRuntime,
    )

    n = P.shape[0]
    reg = obs.default_registry()

    # ---- recovery: kill a durable stream, restore, measure ----------
    tmp = tempfile.mkdtemp(prefix="bench-fault-")
    batch = 256
    dur = DurabilityConfig(dir=tmp, checkpoint_every=4, keep=3)
    rt = StreamRuntime(spec, k, tau=tau, caps=caps,
                       block_size=BLOCK_SIZE, durability=dur)
    for off in range(0, n, batch):
        rt.submit(P[off:off + batch], cats[off:off + batch])
    rt.flush()
    live_fp = rt.latest().fingerprint
    # the "kill": no close(), no parting checkpoint — restore must
    # replay the WAL tail beyond the newest cadence checkpoint
    with Timer() as t_rec:
        back = StreamRuntime.restore(tmp)
    rep = back.restore_report
    parity = back.latest().fingerprint == live_fp
    replay_pps = (
        rep["replayed_points"] / rep["restore_s"]
        if rep["restore_s"] > 0 else 0.0
    )
    # preserve the post-crash replay state as a CI artifact
    art_dir = os.path.join(os.path.dirname(_JSON_PATH),
                           "BENCH_fault_recovery")
    shutil.rmtree(art_dir, ignore_errors=True)
    os.makedirs(art_dir, exist_ok=True)
    back.checkpoint(force=True)
    from repro.serve.diversity import latest_checkpoint
    newest = latest_checkpoint(tmp)
    if newest:
        shutil.copy2(newest, art_dir)
    with open(os.path.join(art_dir, "recovery.json"), "w") as f:
        json.dump(dict(rep, replay_parity=bool(parity),
                       recovery_wall_s=float(t_rec.s)), f, indent=2,
                  default=str)
    back.close()
    rt.close()
    shutil.rmtree(tmp, ignore_errors=True)
    recovery = dict(
        n_ingested=int(n),
        recovery_s=float(t_rec.s),
        replayed_batches=int(rep["replayed_batches"]),
        replayed_points=int(rep["replayed_points"]),
        replay_pps=float(replay_pps),
        replay_parity=bool(parity),
        artifact="BENCH_fault_recovery/",
    )

    # ---- chaos ingest: crashes + retries + one poisoned batch -------
    cbatch = 128
    plan = FaultPlan(7, [
        FaultRule(site="worker.loop", kind="crash", after=2, every=3,
                  times=2),
        FaultRule(site="worker.ingest", kind="error", after=5, every=4,
                  times=4),
        # two consecutive fires exhaust max_retries=1: one poisoned batch
        FaultRule(site="worker.ingest", kind="error", after=24, times=2),
    ])
    rt = StreamRuntime(
        spec, k, tau=tau, caps=caps, block_size=BLOCK_SIZE,
        faults=plan,
        fault_policy=FaultPolicy(max_retries=1, backoff_s=0.01,
                                 on_failure="quarantine",
                                 max_worker_restarts=5),
    )
    c0 = reg.counter("serve.worker.crashes").value
    r0 = reg.counter("serve.worker.restarts").value
    t0 = reg.counter("serve.worker.retries").value
    for off in range(0, n, cbatch):
        rt.submit(P[off:off + cbatch], cats[off:off + cbatch])
    rt.flush()  # quarantine keeps the stream alive: must not raise
    lost = sum(int(b.points.shape[0]) for b in rt.poison)
    chaos = dict(
        crashes=int(reg.counter("serve.worker.crashes").value - c0),
        restarts=int(reg.counter("serve.worker.restarts").value - r0),
        retries=int(reg.counter("serve.worker.retries").value - t0),
        poisoned=len(rt.poison),
        poisoned_points=int(lost),
        stream_continued=bool(rt.n_offered == n - lost and lost > 0),
    )
    rt.close()

    # ---- deadline burst: 4x saturation, degrade-or-shed inside budget
    rt = StreamRuntime(spec, k, tau=tau, caps=caps, block_size=BLOCK_SIZE)
    fe = QueryFrontend(rt)
    rt.ingest(P, cats)
    # a dedicated tenant: its latency histograms (the admission
    # predictor) train on THIS section's warm calls only — the earlier
    # sections' compile-inclusive observations would skew every engine's
    # p95 toward seconds and turn the whole burst into sheds
    tenant = "burst"
    fe.register_tenant(tenant)
    qs_exact = [
        DiversityQuery(k=3, variant="tree" if i % 2 else "star")
        for i in range(6)
    ]
    qs_greedy = [
        dataclasses.replace(q, engine_hint="jit_greedy") for q in qs_exact
    ]
    fe.query_batch(qs_exact, tenant=tenant)  # warm + feed the predictor
    fe.query_batch(qs_greedy, tenant=tenant)
    walls_e, walls_g = [], []
    for _ in range(3):
        with Timer() as te:
            fe.query_batch(qs_exact, tenant=tenant)
        walls_e.append(te.s)
    # enough warm greedy observations that the predictor's p95 rank
    # clears the one compile-inclusive first call (rank ceil(.95n) < n
    # needs n >= 20) — the burst must see the steady-state greedy cost
    for _ in range(20):
        with Timer() as tg:
            fe.query_batch(qs_greedy, tenant=tenant)
        walls_g.append(tg.s)
    L_exact, L_greedy = float(np.min(walls_e)), float(np.min(walls_g))
    # 4x saturation: the budget is a quarter of what exact serving needs
    # (floored so the degraded engine genuinely fits inside it)
    deadline_s = max(L_exact / 4.0, 2.5 * L_greedy, 0.02)
    rounds, per_round = 4, 6
    miss_c = reg.counter("serve.query.deadline_miss", tenant=tenant)
    # materialize the outcome counters up front so the embedded metrics
    # snapshot always carries all three series, zeros included
    reg.counter("serve.query.shed", tenant=tenant)
    reg.counter("serve.query.degraded", tenant=tenant)
    outcomes = {"ok": 0, "degraded": 0, "shed": 0}
    misses = []
    for _ in range(rounds):
        m0 = miss_c.value
        for _ in range(per_round):
            for r in fe.query_batch(qs_exact, tenant=tenant,
                                    deadline_s=deadline_s):
                key = ("shed" if r.shed
                       else "degraded" if r.degraded else "ok")
                outcomes[key] += 1
        misses.append(miss_c.value - m0)
    rt.close()
    total = sum(outcomes.values())
    deadline = dict(
        deadline_s=float(deadline_s),
        exact_batch_s=L_exact,
        greedy_batch_s=L_greedy,
        saturation=4.0,
        queries=int(total),
        ok_fraction=outcomes["ok"] / total,
        degraded_fraction=outcomes["degraded"] / total,
        shed_fraction=outcomes["shed"] / total,
        goodput=(outcomes["ok"] + outcomes["degraded"]) / total,
        deadline_violations=int(min(misses)),
        deadline_misses_by_round=[int(m) for m in misses],
    )
    return dict(recovery=recovery, chaos=chaos, deadline=deadline)


def _replication(P, cats, caps, spec, k: int, tau: int,
                 quick: bool) -> dict:
    """Replication section: WAL-shipped hot standby + primary-kill
    failover + online integrity audit.

    A ``ReplicaSet`` (primary + 1 standby, each with its own WAL) is
    driven through the full stream with a seeded worker crash planted
    mid-ingest on the primary. The write path detects the dead primary,
    promotes the standby (replaying the acked WAL tail first) and
    retries inline — recorded are the failover wall time
    (``failover_s``), acked-batch accounting, and ``failover_parity``:
    the post-failover fingerprint must be bit-identical to a
    single-runtime replay of the same stream (zero acked batches lost,
    the §3 composability argument made operational). Per-batch
    ``observe_lag`` calls populate the
    ``serve.replication.lag_batches`` histogram. An
    ``IntegrityAuditor`` pass over the surviving set closes the run:
    coverage radius vs tau, matroid independence of every delegate
    set, cached pdist spot-checks — ``audit_violations`` must be 0.
    The failover report lands in ``BENCH_failover/`` (CI uploads it).
    """
    import shutil
    import tempfile

    from repro import obs
    from repro.serve.diversity import (
        DiversityQuery,
        FaultPlan,
        FaultPolicy,
        FaultRule,
        IntegrityAuditor,
        ReplicaSet,
        StreamRuntime,
    )

    n = P.shape[0]
    reg = obs.default_registry()
    batch = 256
    n_batches = (n + batch - 1) // batch
    kill_after = max(2, n_batches // 2)
    tmp = tempfile.mkdtemp(prefix="bench-repl-")
    plan = FaultPlan(11, [
        FaultRule(site="worker.loop", kind="crash", after=kill_after,
                  times=1),
    ])
    rs = ReplicaSet.create(
        spec, k, dir=os.path.join(tmp, "replicas"), caps=caps, tau=tau,
        block_size=BLOCK_SIZE, registry=reg, faults=plan,
        fault_policy=FaultPolicy(max_worker_restarts=0),
    )
    lag_obs, max_lag = 0, 0
    for off in range(0, n, batch):
        rs.submit(P[off:off + batch], cats[off:off + batch])
        lags = rs.observe_lag()
        lag_obs += len(lags)
        if lags:
            max_lag = max(max_lag, max(lags.values()))
    rs.flush()
    st = rs.stats()
    lf = rs.last_failover or {}
    # bit-identical parity against a single runtime folding the same
    # stream: the promoted standby replayed WAL records, never points
    ref = StreamRuntime(spec, k, tau=tau, caps=caps,
                        block_size=BLOCK_SIZE)
    for off in range(0, n, batch):
        ref.ingest(P[off:off + batch], cats[off:off + batch])
    ref_fp = ref.refresh(force=True).fingerprint
    ref.close()
    prt = rs.primary.runtime
    parity = bool(prt.n_offered == n and prt.fingerprint == ref_fp)
    # the promoted stack keeps serving: one query through the set
    res = rs.query(DiversityQuery(k=k))
    # online integrity audit over the surviving replicas
    auditor = IntegrityAuditor(rs, registry=reg)
    reports = auditor.audit_once()
    audit = dict(
        checks=int(auditor.total_checks),
        violations=int(auditor.total_violations),
        reports=[
            dict(replica=r.replica, checks=int(r.checks),
                 violations=list(r.violations))
            for r in reports
        ],
    )
    # preserve the failover report as a CI artifact
    art_dir = os.path.join(os.path.dirname(_JSON_PATH), "BENCH_failover")
    shutil.rmtree(art_dir, ignore_errors=True)
    os.makedirs(art_dir, exist_ok=True)
    with open(os.path.join(art_dir, "failover.json"), "w") as f:
        json.dump(dict(
            last_failover=lf, stats=st, failover_parity=parity,
            audit=audit, query_diversity=float(res.diversity),
        ), f, indent=2, default=str)
    rs.close()
    shutil.rmtree(tmp, ignore_errors=True)
    return dict(
        n_ingested=int(n),
        n_standbys=1,
        failovers=int(st["failovers"]),
        failover_s=float(lf.get("duration_s", -1.0)),
        promoted=lf.get("promoted"),
        retired=lf.get("retired"),
        acked_seq=int(st["acked_seq"]),
        acked_batches=int(st["acked_batches"]),
        failover_parity=parity,
        lag_observations=int(lag_obs),
        max_lag_batches=int(max_lag),
        reseeds=int(st["reseeds"]),
        audit_checks=audit["checks"],
        audit_violations=audit["violations"],
        artifact="BENCH_failover/",
    )


def _bench(quick: bool, num_shards: int | None = None) -> dict:
    import jax

    from repro import obs
    from repro.core import solve_dmmc
    from repro.serve.diversity import DiversityQuery, DiversityService

    # observability: start every bench run from zeroed metrics and an
    # empty trace buffer so the embedded snapshot/trace describe THIS run
    obs.reset()
    census = obs.recompile_watch()  # never reset: the full-run census
    steady = obs.RecompileWatch()  # windowed: warmed measurement gates
    steady_total = 0  # compiles observed inside warmed measured windows

    n = 4000 if quick else 20000
    k, tau, batch = 8, 32, 512
    P, cats, caps, spec = songs_like(n)
    if num_shards is None:
        num_shards = default_num_shards()
    S = int(num_shards)

    def mk(**kw):
        return lambda: DiversityService(
            spec, k, tau=tau, caps=caps, block_size=BLOCK_SIZE, **kw
        )

    factories = {
        "unsharded": mk(),
        "sharded_auto": mk(num_shards=S),
        "sharded_vmap": mk(num_shards=S, placement="vmap"),
        "sharded_shard_map": mk(num_shards=S, placement="shard_map"),
        "sharded_pipeline": mk(num_shards=S, placement="pipeline"),
    }
    pps, svcs = _steady_ingest(factories, P, cats, n, batch,
                               steady_watch=steady)
    steady_total += steady.total()
    svc = svcs["unsharded"]
    svc_sh = svcs["sharded_auto"]
    ingest_pps = pps["unsharded"]
    sharded_pps = pps["sharded_auto"]
    sharded_speedup = sharded_pps / ingest_pps

    # true process-cold first query: pays the full trace+compile+pdist —
    # measured before ANYTHING else in the process solves (the offline
    # driver below shares solver/pdist jits and would partially warm it)
    with Timer() as t_first:
        res = svc.query(DiversityQuery(k=k))
    # cold: offline driver from raw points (coreset + pdist + solve)
    with Timer() as t_cold:
        sol = solve_dmmc(P, k, spec, cats=cats, caps=caps, tau=tau,
                         setting="streaming")
    # warmup absorbs that cost: a fresh service over the same stream calls
    # warmup() before its first query (in a cold process the warmup wall
    # equals the compile it absorbs; in this process it reuses the jit
    # cache — exactly what a pre-warmed serving fleet sees)
    svc_w = factories["unsharded"]()
    svc_w.ingest(P, cats)
    with Timer() as t_wup:
        svc_w.warmup(ks=(k,), query_batch_sizes=(1, 32))
    with Timer() as t_firstw:
        svc_w.query(DiversityQuery(k=k))
    sharded_res = svc_sh.query(DiversityQuery(k=k))

    # warm single-query latency on the cached matrix (median of reps)
    reps = 9 if quick else 20
    steady.reset()  # warm window: the shape/matrix are already compiled
    lat = []
    for _ in range(reps):
        t0 = time.perf_counter()
        res = svc.query(DiversityQuery(k=k))
        lat.append(time.perf_counter() - t0)
    steady_total += steady.total()
    warm_s = float(np.median(lat))
    assert res.from_cache and svc.cache.stats.builds == 1

    # batched heterogeneous queries (32) against one cache entry
    qs = [
        DiversityQuery(
            k=2 + i % 7,
            caps=None if i % 2 else tuple(np.maximum(1, caps // 2).tolist()),
            allowed_cats=None if i % 3 else frozenset(range(8)),
        )
        for i in range(32)
    ]
    svc.query_batch(qs)  # compile the vmapped solver for this shape
    steady.reset()
    b_lat = []
    for _ in range(reps):
        with Timer() as t_b:
            out = svc.query_batch(qs)
        b_lat.append(t_b.s)
    steady_total += steady.total()
    assert svc.cache.stats.builds == 1, "batched path rebuilt the matrix"
    qps = len(out) / float(np.min(b_lat))

    # ---- per-engine batched QPS + eligibility mix (solver registry) ----
    def _batch_qps(svc_, qs_, engine_="auto", reps_=3):
        nonlocal steady_total
        svc_.query_batch(qs_, engine=engine_)  # compile/warm this shape
        steady.reset()  # the warm call above absorbed any compile
        lats = []
        for _ in range(reps_):
            with Timer() as t_:
                got = svc_.query_batch(qs_, engine=engine_)
            lats.append(t_.s)
        steady_total += steady.total()
        return len(got) / float(np.min(lats)), got

    def _mix(results) -> dict:
        counts: dict[str, int] = {}
        for r_ in results:
            counts[r_.engine] = counts.get(r_.engine, 0) + 1
        return {e: c / len(results) for e, c in sorted(counts.items())}

    # sum under partition: the historical fast cell
    qs_sum = [DiversityQuery(k=2 + i % 7) for i in range(32)]
    qps_part_jit, _ = _batch_qps(svc, qs_sum, "jit_sum", reps)
    qps_part_host, _ = _batch_qps(svc, qs_sum, "host")
    # star/tree under partition: exact host vs opt-in vmapped greedy
    qs_st = [
        DiversityQuery(k=3, variant="tree" if i % 2 else "star")
        for i in range(8)
    ]
    qs_st_hint = [
        dataclasses.replace(q, engine_hint="jit_greedy") for q in qs_st
    ]
    qps_st_greedy, out_st = _batch_qps(svc, qs_st_hint, "auto", reps)
    qps_st_host, _ = _batch_qps(svc, qs_st, "host")
    # sum under transversal: the new jit cell (was 100% host before the
    # solver-engine refactor)
    n_tv = max(1000, n // 4)
    Ptv, cats_tv, _, spec_tv = songs_multilabel(n_tv)
    svc_tv = DiversityService(spec_tv, k, tau=tau, block_size=BLOCK_SIZE)
    svc_tv.ingest(Ptv, cats_tv)
    qs_tv = [DiversityQuery(k=2 + i % 4) for i in range(32)]
    qps_tv_jit, out_tv = _batch_qps(svc_tv, qs_tv, "auto", reps)
    qps_tv_host, _ = _batch_qps(svc_tv, qs_tv, "host")
    res_tv = svc_tv.query(DiversityQuery(k=k))

    batched_qps_by_engine = dict(
        partition_sum_jit_sum=float(qps_part_jit),
        partition_sum_host=float(qps_part_host),
        partition_startree_jit_greedy=float(qps_st_greedy),
        partition_startree_host=float(qps_st_host),
        transversal_sum_auto=float(qps_tv_jit),
        transversal_sum_host=float(qps_tv_host),
    )
    # a heterogeneous auto batch: the registry partitions it per query
    out_mixed = svc.query_batch(qs_sum[:24] + qs_st)
    engine_mix = dict(
        partition_auto=_mix(out_mixed),
        transversal_auto=_mix(out_tv),
        startree_hint=_mix(out_st),
    )

    # ---- obs overhead A/B: enabled vs disabled, interleaved floors ----
    # same methodology as every other ratio here: alternate the registry
    # switch per rep so both arms share the host weather, gate on floors.
    # The service is saturated (5 full stream passes), so re-ingesting a
    # seen batch is the steady-state no-op and the cache entry stays warm.
    ob_reps = 40 if quick else 60
    ing_ab = {True: [], False: []}
    qry_ab = {True: [], False: []}
    arm_order = (True, False)
    for target, ab in ((svc.ingest, ing_ab), (None, qry_ab)):
        for _ in range(ob_reps):
            arm_order = arm_order[::-1]  # alternate: no ordering bias
            for enabled in arm_order:
                obs.set_enabled(enabled)
                with Timer() as t_ab:
                    if target is not None:
                        target(P[:batch], cats[:batch])
                    else:
                        svc.query_batch(qs)
                ab[enabled].append(t_ab.s)
    obs.set_enabled(True)
    obs_overhead = dict(
        # (enabled floor / disabled floor) - 1: the fraction of warmed
        # ingest / batched-query wall the metrics+span layer costs
        ingest_overhead=float(
            np.min(ing_ab[True]) / np.min(ing_ab[False]) - 1.0
        ),
        batched_qps_overhead=float(
            np.min(qry_ab[True]) / np.min(qry_ab[False]) - 1.0
        ),
        reps=int(ob_reps),
    )

    # fault tolerance: recovery, chaos ingest, deadline burst (before the
    # mixed workload so the trace ring still ends on the full span story)
    fault = _fault_tolerance(P, cats, caps, spec, k, tau, quick)

    # replication: hot standby, primary-kill failover, integrity audit
    repl = _replication(P, cats, caps, spec, k, tau, quick)

    # concurrent ingest+query + multi-tenant fan-out (its own runtime so
    # the contention window doesn't perturb the services measured above)
    mixed = _mixed_workload(P, cats, caps, spec, k, tau, quick,
                            ingest_pps)

    # drop the Chrome trace artifact LAST: the mixed-workload section is
    # the one that produces every span kind (submit -> worker_ingest ->
    # publish on the ingest side, query_batch -> ... -> solve ->
    # solve.pack/h2d/run/value on the read side), and the ring buffer
    # keeps the newest spans under overload
    trace_path = _JSON_PATH.replace(".json", ".trace.json")
    obs.dump_trace(trace_path)
    steady.close()

    speedup = t_cold.s / warm_s
    dev = jax.devices()[0]
    return dict(
        n=n, k=k, tau=tau,
        coreset_size=int(res.coreset_size),
        ingest_points_per_s=float(ingest_pps),
        ingest_points_per_s_sharded=float(sharded_pps),
        sharded_speedup=float(sharded_speedup),
        # the vmap drive's ratio, gated separately: on CPU the auto
        # placement (pipeline) shares the unsharded executable, so its
        # ratio alone would never catch a regression of the branchless
        # vmapped scan itself (the 0.22x failure mode this PR fixed)
        sharded_speedup_vmap=float(pps["sharded_vmap"] / ingest_pps),
        sharded_placement=svc_sh.placement,
        # every placement measured by its own dedicated service — the auto
        # service's number lives in ingest_points_per_s_sharded, never
        # overwriting a placement's entry
        ingest_pps_by_placement={
            "vmap": float(pps["sharded_vmap"]),
            "shard_map": float(pps["sharded_shard_map"]),
            "pipeline": float(pps["sharded_pipeline"]),
        },
        cold_solve_s=float(t_cold.s),
        first_query_cold_s=float(t_first.s),
        warmup_s=float(t_wup.s),
        first_query_warmed_s=float(t_firstw.s),
        warm_query_s=warm_s,
        warm_speedup_vs_cold=float(speedup),
        batched_qps=float(qps),
        batch_size=len(out),
        batched_qps_by_engine=batched_qps_by_engine,
        engine_mix=engine_mix,
        mixed_workload=mixed,
        fault_tolerance=fault,
        replication=repl,
        transversal_n=int(n_tv),
        transversal_coreset_size=int(res_tv.coreset_size),
        offline_diversity=float(sol.diversity),
        warm_diversity=float(res.diversity),
        sharded_diversity=float(sharded_res.diversity),
        sharded_coreset_size=int(sharded_res.coreset_size),
        pdist_builds=int(svc.cache.stats.builds),
        cache_hits=int(svc.cache.stats.hits),
        # observability artifacts: the full metrics snapshot of this run,
        # the recompile census keyed by compile region (bucketed shape),
        # and the warmed-window recompile count gated == 0 by --check
        metrics=obs.metrics_snapshot(),
        recompiles_by_key=census.by_key(),
        steady_state_recompiles=int(steady_total),
        obs_overhead=obs_overhead,
        trace_path=os.path.basename(trace_path),
        ingest_batch=batch,
        block_size=BLOCK_SIZE,
        num_shards=S,
        num_shards_derived=int(default_num_shards()),
        device_count=int(jax.device_count()),
        backend=str(jax.default_backend()),
        device_kind=str(getattr(dev, "device_kind", dev.platform)),
        machine=f"{_platform.system()}-{_platform.machine()}",
        host=_platform.node(),  # distinguishes physical machines whose
                                # backend/device_kind/arch all read the same
    )


def check(tolerance: float = 0.2, quick: bool = True) -> int:
    """Rerun the quick bench and compare against the committed artifact.

    Returns a process exit code: 1 on failure. Gates:

    * config drift (n/k/tau, batch/block constants) always fails, forcing
      a re-baseline; ``num_shards`` is re-run at the *committed* value so
      shard-count-derived machines stay comparable;
    * ``ingest_points_per_s`` / ``batched_qps`` floors (committed value
      minus ``tolerance``) — downgraded to report-only when the
      environment (backend/device/arch) differs from the artifact's;
    * ``sharded_speedup``: the committed artifact must carry >= 1.0
      (sharding must never be recorded as a slowdown again — it shipped
      at 0.22x once), and the re-measured ratio must stay above
      ``1.0 - tolerance``. The ratio is machine-relative, so this gate is
      NOT downgraded on environment changes; the tolerance absorbs
      measurement noise around parity on single-core hosts, where equal
      work is the physical floor;
    * engine-routing mix (machine-independent) as before;
    * mixed-workload ratios (machine-relative, gated everywhere):
      ``contention_p95_ratio <= 2.0`` and
      ``multi_tenant_min_ratio >= 0.8`` over >= 4 tenants; a missing
      ``mixed_workload`` section fails outright.

    Every check run also drops its fresh measurement at
    ``BENCH_serve.check.json`` (CI uploads it as a workflow artifact).
    """
    if not os.path.exists(_JSON_PATH):
        print(f"check: no committed {_JSON_PATH}; nothing to compare")
        return 0
    with open(_JSON_PATH) as f:
        old = json.load(f)
    new = _bench(quick, num_shards=old.get("num_shards"))
    # drop the fresh measurement beside the committed artifact: CI uploads
    # it as a workflow artifact so every run's numbers are inspectable
    with open(_JSON_PATH.replace(".json", ".check.json"), "w") as f:
        json.dump(new, f, indent=2)
    # config keys only ever change via a code edit — that must fail the
    # gate (forcing a re-baseline with --json), not silently disable it
    rc = 0
    for key in ("n", "k", "tau", "ingest_batch", "block_size", "num_shards"):
        if key in old and old[key] != new[key]:
            print(f"check: CONFIG CHANGED: {key} "
                  f"(committed {old[key]!r} vs here {new[key]!r}); "
                  f"re-baseline with `serve_bench --quick --json`")
            rc = 1
    # environment keys relax the absolute-throughput gates: those aren't
    # comparable across backends/arch classes. "host" is recorded for
    # provenance but never un-gates (CI container hostnames are ephemeral).
    same_env = True
    for key in ("backend", "device_kind", "machine"):
        if key in old and old[key] != new[key]:
            print(f"check: note: {key} differs "
                  f"(committed {old[key]!r} vs here {new[key]!r})")
            same_env = False
    if old.get("host") != new["host"]:
        print(f"check: note: host differs (committed {old.get('host')!r} vs "
              f"here {new['host']!r}); re-baseline with "
              f"`serve_bench --quick --json` if this machine is slower")
    for metric in ("ingest_points_per_s", "batched_qps"):
        if metric not in old:
            print(f"check: {metric}: no committed value, skipping")
            continue
        floor = old[metric] * (1.0 - tolerance)
        ok = new[metric] >= floor
        verdict = "OK" if ok else (
            "REGRESSION" if same_env else "BELOW FLOOR (env differs, not gated)"
        )
        print(f"check: {metric}: committed {old[metric]:.0f}, "
              f"now {new[metric]:.0f}, floor {floor:.0f} -> {verdict}")
        if not ok and same_env:
            rc = 1
    # sharded_speedup: a machine-relative ratio, gated everywhere
    if "sharded_speedup" in old:
        committed = old["sharded_speedup"]
        if committed < 1.0:
            print(f"check: sharded_speedup: committed artifact carries "
                  f"{committed:.2f} < 1.0 -> BASELINE REGRESSION "
                  f"(sharded ingest must not be re-baselined as a slowdown)")
            rc = 1
        floor = 1.0 - tolerance
        ok = new["sharded_speedup"] >= floor
        print(f"check: sharded_speedup: committed {committed:.2f}, "
              f"now {new['sharded_speedup']:.2f}, floor {floor:.2f} -> "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            rc = 1
    # the vmap drive's ratio: on CPU the auto placement runs the unsharded
    # executable per batch, so only this gate protects the branchless
    # vmapped scan from sliding back toward the historical 0.22x
    if "sharded_speedup_vmap" in old:
        floor = old["sharded_speedup_vmap"] * (1.0 - tolerance)
        ok = new["sharded_speedup_vmap"] >= floor
        print(f"check: sharded_speedup_vmap: committed "
              f"{old['sharded_speedup_vmap']:.2f}, "
              f"now {new['sharded_speedup_vmap']:.2f}, floor {floor:.2f} "
              f"-> {'OK' if ok else 'REGRESSION'}")
        if not ok:
            rc = 1
    # mixed-workload gates (machine-relative ratios, enforced everywhere):
    # queries served during active ingestion must stay within 2x the idle
    # warm p95 (the epoch-snapshot decoupling contract), and every tenant
    # fanned out from the single stream must serve cached QPS within 20%
    # of the single-tenant baseline (fan-out is cache-shaped, not
    # stream-shaped)
    mw = new.get("mixed_workload", {})
    if mw:
        ratio = mw["contention_p95_ratio"]
        ok = ratio <= 2.0
        print(f"check: mixed contention_p95_ratio = {ratio:.2f} "
              f"(idle p95 {mw['idle_p95_s'] * 1e3:.2f}ms, contended p95 "
              f"{mw['contended_p95_s'] * 1e3:.2f}ms, ceiling 2.00) -> "
              f"{'OK' if ok else 'CONTENTION REGRESSION'}")
        if not ok:
            rc = 1
        mtr = mw["multi_tenant_min_ratio"]
        ok = mtr >= 0.8 and mw["tenant_count"] >= 4
        print(f"check: mixed multi_tenant_min_ratio = {mtr:.2f} over "
              f"{mw['tenant_count']} tenants (floor 0.80, >= 4 tenants) "
              f"-> {'OK' if ok else 'FANOUT REGRESSION'}")
        if not ok:
            rc = 1
    else:  # the section must exist: its absence is itself a regression
        print("check: mixed_workload section missing -> REGRESSION")
        rc = 1
    # fault-tolerance gates (machine-relative / boolean, enforced
    # everywhere): restore must rebuild the exact stream within bounded
    # time, the chaos ingest must survive its injected faults, and the
    # 4x-saturation deadline burst must answer inside the budget
    ft = new.get("fault_tolerance", {})
    if ft:
        rec = ft["recovery"]
        ok = (rec["replay_parity"] and rec["recovery_s"] <= 60.0
              and rec["replay_pps"] > 0)
        print(f"check: fault recovery: parity={rec['replay_parity']}, "
              f"recovery {rec['recovery_s']:.2f}s (ceiling 60), replay "
              f"{rec['replay_pps']:.0f} pps over "
              f"{rec['replayed_batches']} batches -> "
              f"{'OK' if ok else 'RECOVERY REGRESSION'}")
        if not ok:
            rc = 1
        ch = ft["chaos"]
        ok = ch["stream_continued"] and ch["crashes"] >= 1
        print(f"check: fault chaos: crashes {ch['crashes']}, restarts "
              f"{ch['restarts']}, retries {ch['retries']}, poisoned "
              f"{ch['poisoned']}, stream_continued="
              f"{ch['stream_continued']} -> "
              f"{'OK' if ok else 'SUPERVISION REGRESSION'}")
        if not ok:
            rc = 1
        dl = ft["deadline"]
        ok = dl["deadline_violations"] == 0 and dl["goodput"] >= 0.5
        print(f"check: fault deadline: {dl['saturation']:.0f}x burst, "
              f"budget {dl['deadline_s'] * 1e3:.0f}ms, goodput "
              f"{dl['goodput']:.2f} (floor 0.50), violations "
              f"{dl['deadline_violations']} (min over rounds, must be 0) "
              f"-> {'OK' if ok else 'DEADLINE REGRESSION'}")
        if not ok:
            rc = 1
    else:
        print("check: fault_tolerance section missing -> REGRESSION")
        rc = 1
    # replication gates (machine-relative / boolean, enforced
    # everywhere): a mid-ingest primary kill must promote the standby
    # within bounded time with a bit-identical stream and zero acked
    # batches lost, the lag histogram must carry observations, and the
    # integrity audit of the surviving set must be clean
    rp = new.get("replication", {})
    if rp:
        ok = (rp["failover_parity"] and rp["failovers"] >= 1
              and 0.0 <= rp["failover_s"] <= 5.0)
        print(f"check: replication failover: failovers={rp['failovers']}, "
              f"{rp['failover_s']:.2f}s (ceiling 5), "
              f"parity={rp['failover_parity']}, "
              f"promoted={rp.get('promoted')}, acked "
              f"{rp['acked_batches']} batches -> "
              f"{'OK' if ok else 'FAILOVER REGRESSION'}")
        if not ok:
            rc = 1
        ok = rp["lag_observations"] > 0
        print(f"check: replication lag histogram: "
              f"{rp['lag_observations']} observations, max lag "
              f"{rp['max_lag_batches']} batches -> "
              f"{'OK' if ok else 'LAG HISTOGRAM EMPTY'}")
        if not ok:
            rc = 1
        ok = rp["audit_violations"] == 0 and rp["audit_checks"] > 0
        print(f"check: replication audit: {rp['audit_checks']} checks, "
              f"{rp['audit_violations']} violations (must be 0) -> "
              f"{'OK' if ok else 'INTEGRITY REGRESSION'}")
        if not ok:
            rc = 1
    else:
        print("check: replication section missing -> REGRESSION")
        rc = 1
    # steady-state recompile gate (machine-independent, gated everywhere):
    # the warmed measurement windows must compile NOTHING — a recompile
    # there means a jit cache key (bucketed shape, static arg) failed to
    # hold, silently turning a microsecond path into a multi-second one
    ssr = new.get("steady_state_recompiles")
    ok = ssr == 0
    print(f"check: steady_state_recompiles = {ssr} -> "
          f"{'OK' if ok else 'RECOMPILE REGRESSION'}")
    if not ok:
        rc = 1
        for key, cnt in sorted(new.get("recompiles_by_key", {}).items()):
            print(f"check:   compile census: {key} x{cnt}")
    # metrics-presence gate: the embedded snapshot must carry the serving
    # story — nonzero ingest and query histograms, per-engine solve series
    met = new.get("metrics", {})

    def _hist_count(prefix: str) -> int:
        return sum(
            d.get("count") or 0
            for key, d in met.items() if key.startswith(prefix)
        )

    ing_obs = _hist_count("serve.ingest.latency_s")
    qry_obs = _hist_count("serve.query.latency_s")
    solve_engines = sorted(
        key for key, d in met.items()
        if key.startswith("serve.solve.latency_s")
        and "engine=" in key and (d.get("count") or 0) > 0
    )
    ok = ing_obs > 0 and qry_obs > 0 and bool(solve_engines)
    print(f"check: metrics snapshot: ingest observations {ing_obs}, "
          f"query observations {qry_obs}, per-engine solve series "
          f"{len(solve_engines)} -> "
          f"{'OK' if ok else 'METRICS MISSING'}")
    if not ok:
        rc = 1
    ov = new.get("obs_overhead", {})
    if ov:  # report-only: the ratio is noisy on shared hosts
        print(f"check: obs_overhead: ingest "
              f"{ov['ingest_overhead']:+.1%}, batched "
              f"{ov['batched_qps_overhead']:+.1%} (target <= 3%)")
    # eligibility-mix gate (machine-independent): the jit engines must keep
    # covering their (variant x matroid) cells — a dispatch regression that
    # silently routes transversal or star/tree batches back to 100% host
    # fails even when absolute throughput is not comparable
    mix = new.get("engine_mix", {})
    for workload, engine_name in (
        ("partition_auto", "jit_sum"),
        ("transversal_auto", "jit_sum"),
        ("startree_hint", "jit_greedy"),
    ):
        frac = mix.get(workload, {}).get(engine_name, 0.0)
        ok = frac > 0.0
        print(f"check: engine_mix[{workload}][{engine_name}] = {frac:.2f} "
              f"-> {'OK' if ok else 'ROUTING REGRESSION'}")
        if not ok:
            rc = 1
    return rc


def main(quick: bool = False, emit_json: bool = False,
         num_shards: int | None = None):
    r = _bench(quick, num_shards=num_shards)
    if emit_json:
        with open(_JSON_PATH, "w") as f:
            json.dump(r, f, indent=2)
    yield csv_line("serve_ingest", 1e6 / r["ingest_points_per_s"],
                   f"pps={r['ingest_points_per_s']:.0f} "
                   f"block={r['block_size']}")
    yield csv_line("serve_ingest_sharded",
                   1e6 / r["ingest_points_per_s_sharded"],
                   f"pps={r['ingest_points_per_s_sharded']:.0f} "
                   f"shards={r['num_shards']} "
                   f"speedup={r['sharded_speedup']:.2f}x "
                   f"placement={r['sharded_placement']}")
    for pl, pv in r["ingest_pps_by_placement"].items():
        yield csv_line(f"serve_ingest_sharded_{pl}", 1e6 / pv,
                       f"pps={pv:.0f}")
    yield csv_line("serve_cold_solve", r["cold_solve_s"] * 1e6,
                   f"n={r['n']}")
    yield csv_line("serve_first_query_cold", r["first_query_cold_s"] * 1e6,
                   "trace+compile+pdist")
    yield csv_line("serve_first_query_warmed",
                   r["first_query_warmed_s"] * 1e6,
                   f"warmup={r['warmup_s']:.2f}s")
    yield csv_line("serve_warm_query", r["warm_query_s"] * 1e6,
                   f"speedup={r['warm_speedup_vs_cold']:.1f}x")
    yield csv_line("serve_batched", 1e6 / r["batched_qps"],
                   f"qps={r['batched_qps']:.0f} batch={r['batch_size']}")
    for cell, cqps in r["batched_qps_by_engine"].items():
        yield csv_line(f"serve_batched_{cell}", 1e6 / cqps,
                       f"qps={cqps:.0f}")
    for workload, mix in r["engine_mix"].items():
        pretty = " ".join(f"{e}={frac:.2f}" for e, frac in mix.items())
        yield csv_line(f"serve_mix_{workload}", 0.0, pretty)
    mw = r["mixed_workload"]
    yield csv_line("serve_query_idle_p95", mw["idle_p95_s"] * 1e6,
                   f"p50={mw['idle_p50_s'] * 1e6:.0f}us")
    yield csv_line("serve_query_contended_p95", mw["contended_p95_s"] * 1e6,
                   f"p50={mw['contended_p50_s'] * 1e6:.0f}us "
                   f"ratio={mw['contention_p95_ratio']:.2f}x "
                   f"ingest_pps={mw['contended_ingest_pps']:.0f}")
    for name, tqps in mw["tenant_qps"].items():
        yield csv_line(f"serve_tenant_{name}", 1e6 / tqps,
                       f"qps={tqps:.0f} "
                       f"min_ratio={mw['multi_tenant_min_ratio']:.2f}")
    ft = r["fault_tolerance"]
    yield csv_line("serve_recovery", ft["recovery"]["recovery_s"] * 1e6,
                   f"replay_pps={ft['recovery']['replay_pps']:.0f} "
                   f"parity={ft['recovery']['replay_parity']} "
                   f"batches={ft['recovery']['replayed_batches']}")
    yield csv_line("serve_chaos", 0.0,
                   f"crashes={ft['chaos']['crashes']} "
                   f"retries={ft['chaos']['retries']} "
                   f"poisoned={ft['chaos']['poisoned']} "
                   f"continued={ft['chaos']['stream_continued']}")
    yield csv_line("serve_deadline", ft["deadline"]["deadline_s"] * 1e6,
                   f"goodput={ft['deadline']['goodput']:.2f} "
                   f"degraded={ft['deadline']['degraded_fraction']:.2f} "
                   f"shed={ft['deadline']['shed_fraction']:.2f} "
                   f"violations={ft['deadline']['deadline_violations']}")
    rp = r["replication"]
    yield csv_line("serve_failover", rp["failover_s"] * 1e6,
                   f"failovers={rp['failovers']} "
                   f"parity={rp['failover_parity']} "
                   f"promoted={rp['promoted']} "
                   f"acked={rp['acked_batches']}")
    yield csv_line("serve_replication_lag", 0.0,
                   f"max_lag={rp['max_lag_batches']} "
                   f"observations={rp['lag_observations']} "
                   f"reseeds={rp['reseeds']}")
    yield csv_line("serve_audit", 0.0,
                   f"checks={rp['audit_checks']} "
                   f"violations={rp['audit_violations']}")
    yield csv_line("serve_obs_overhead", 0.0,
                   f"ingest={r['obs_overhead']['ingest_overhead']:+.1%} "
                   f"batched={r['obs_overhead']['batched_qps_overhead']:+.1%} "
                   f"steady_recompiles={r['steady_state_recompiles']}")
    if mw["contention_p95_ratio"] > 2.0:
        yield csv_line("serve_CONTENTION_ABOVE_2X", 0.0,
                       f"{mw['contention_p95_ratio']:.2f}x")
    if r["warm_speedup_vs_cold"] < 5.0:
        yield csv_line("serve_SPEEDUP_BELOW_5X", 0.0,
                       f"{r['warm_speedup_vs_cold']:.2f}x")
    if r["sharded_speedup"] < 1.0:
        yield csv_line("serve_SHARDED_BELOW_1X", 0.0,
                       f"{r['sharded_speedup']:.2f}x")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count for the sharded configs "
                         "(default: derived from devices/cpus)")
    ap.add_argument("--check", action="store_true",
                    help="compare a fresh --quick run against the committed "
                         "BENCH_serve.json; exit 1 on >20%% regression")
    args = ap.parse_args()
    enable_compile_cache()
    if args.check:
        sys.exit(check())
    print("name,us_per_call,derived")
    for line in main(quick=args.quick, emit_json=args.json,
                     num_shards=args.shards):
        print(line, flush=True)
