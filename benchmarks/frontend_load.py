"""Frontend load benchmark: closed-loop concurrent clients against the
query frontend, coalesced vs per-call, mixed tenants.

    PYTHONPATH=src python -m benchmarks.frontend_load [--quick] [--json]

``--json`` writes a ``BENCH_frontend.json`` artifact (repo root), the
query-path companion to ``BENCH_serve.json``: it records aggregate QPS,
p50/p99 latency and deadline outcomes at 1/4/16 concurrent mixed-tenant
clients for two arms over the SAME published stream —

* **coalesced**: the default ``QueryFrontend`` (adaptive micro-batch
  window, tenant-sharded dispatcher pool, cost-model routing);
  concurrent callers merge into pow-2-bucketed vmapped solves, stacked
  across tenants into one device dispatch where the engine allows;
* **per-call**: an identical frontend with ``CoalesceConfig(enabled=
  False)`` — every call runs the historical direct path alone.

The coalesced arm runs the serving DEFAULTS (Little's-law adaptive
window, ``dispatchers = min(4, cpu)``): the bench measures what ships,
and the artifact embeds the controller's window-size-over-time trace
(``window_trace``) so its dynamics — solo-collapse at 1 client, widening
under the 16-client burst — are inspectable from the CI artifact.

Methodology mirrors ``serve_bench``: both arms are driven *interleaved*
round-by-round (same host weather, so their ratio is robust to scheduler
noise), after a warmup that pays every jit compile at the measured
pow-2 (B, k) buckets and calibrates both arms' cost models, so the
measurement window is steady state (recompiles there would poison p99
and the cost model alike). QPS is the best round (the stable estimator
on a noisy shared host); the tail gate ``p99 <= 2 x p50`` and the
deadline gate use the min over rounds, like the serve bench's deadline
burst — one scheduler burst cannot fail the gate, a real regression
shifts every round.

Clients are closed-loop threads: each issues 1-2-query batches (k
alternating across two pow-2 buckets) on one of four tenants fanned out
from the single stream (default / cosine / uniform / uniform-cosine),
half the calls carrying a generous ``deadline_s`` — the bench asserts
the window never holds a call past its deadline (violations gated 0).

``benchmarks.run --check`` reruns the quick configuration and gates:

* the *committed* artifact must carry ``speedup_16 >= 2.0`` (coalescing
  must never be re-baselined as a no-win — that is the tentpole);
* the *committed* artifact must carry ``speedup_4 > 1.0``: moderate
  concurrency paid for the window before PR 10 (~0.8x); with stacked
  cross-tenant dispatch and the adaptive window it must be a win, and
  may never be re-baselined back into a loss;
* the re-measured ``speedup_16`` must stay >= 1.0 (machine-relative
  ratio, enforced everywhere: merged dispatch may never be slower than
  16 solo dispatches);
* ``p99_p50_ratio_4 <= 2.0`` (min over rounds, coalesced arm at 4
  clients): the window must not fatten the tail at moderate load;
* ``deadline_violations == 0`` (min over rounds) and zero sheds of
  in-budget calls;
* at 16 clients the coalescer must have actually merged calls
  (``coalesced_calls > 0`` — machine-independent routing gate);
* absolute ``coalesced_qps_16`` floor vs the committed value, relaxed
  to report-only when the environment (backend/device/arch) differs.

Every check run drops its fresh measurement at
``BENCH_frontend.check.json`` (CI uploads it as a workflow artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import platform as _platform
import sys
import threading
import time

import numpy as np

from repro.compile_cache import enable_compile_cache

from .common import csv_line, songs_like

LEVELS = (1, 4, 16)
DEADLINE_S = 5.0  # generous: warm solves are ms-scale, violations gate 0
K_BUCKETS = (3, 5)  # pow-2 k buckets 4 and 8
WARM_BATCHES = (1, 2, 4, 8, 16, 32)  # covers every merged pow-2 B bucket

_JSON_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_frontend.json",
)


def _build(n: int, k: int, tau: int):
    """One published stream + two frontend arms (coalesced / per-call)
    with identical 4-tenant fan-out over it."""
    from repro.core.matroid import MatroidSpec
    from repro.serve.diversity import (
        CoalesceConfig,
        QueryFrontend,
        StreamRuntime,
    )

    P, cats, caps, spec = songs_like(n)
    rt = StreamRuntime(spec, k, tau=tau, caps=caps)
    rt.ingest(P, cats)
    arms = {
        # serving defaults on purpose: adaptive window + dispatcher pool
        "coalesced": QueryFrontend(rt, coalesce=CoalesceConfig()),
        "percall": QueryFrontend(rt, coalesce=CoalesceConfig(enabled=False)),
    }
    uspec = MatroidSpec("uniform")
    for fe in arms.values():
        fe.register_tenant("cosine", metric="cosine")
        fe.register_tenant("uniform", spec=uspec)
        fe.register_tenant("uniform-cos", spec=uspec, metric="cosine")
    names = ["default", "cosine", "uniform", "uniform-cos"]
    return rt, arms, names


def _warm(fe, names) -> None:
    """Pay every compile + calibrate the cost model before measuring.

    Engine-pinned passes compile the jit cells at every pow-2 (B, k)
    bucket a merged group can reach (16 clients x 2 queries max) for
    both matroid views; the repeated auto passes run post-compile so
    ``CostModel.observe`` records honest steady-state latencies (the
    frontend skips observations for solves that compiled anything).
    """
    from repro.serve.diversity import DiversityQuery

    for name in names:  # build each tenant's cache entry once
        fe.query_batch([DiversityQuery(k=max(K_BUCKETS))], tenant=name)
    for tenant in ("default", "uniform"):  # one per matroid view
        for kq in K_BUCKETS:
            for b in WARM_BATCHES:
                qs = [DiversityQuery(k=kq)] * b
                for eng in ("jit_sum", "host"):
                    fe.query_batch(qs, tenant=tenant, engine=eng)
                fe.query_batch(qs, tenant=tenant)  # calibrate auto cells
                fe.query_batch(qs, tenant=tenant)


def _run_level(fe, names, level: int, iters: int) -> dict:
    """One closed-loop round: ``level`` client threads x ``iters`` calls.

    Mixed shapes on purpose — B alternates 1/2 and k across two pow-2
    buckets per client, so a merged group spans sub-batches exactly like
    real mixed traffic (and the parity suite's shapes)."""
    from repro.serve.diversity import DiversityQuery

    lock = threading.Lock()
    lats: list[float] = []
    viol = sheds = total_q = 0
    barrier = threading.Barrier(level + 1)

    def client(i: int) -> None:
        nonlocal viol, sheds, total_q
        my_lats, my_viol, my_sheds, my_q = [], 0, 0, 0
        barrier.wait()
        for it in range(iters):
            b = 1 + (it + i) % 2
            qs = [DiversityQuery(k=K_BUCKETS[(it + i + j) % 2])
                  for j in range(b)]
            dl = DEADLINE_S if it % 2 == 0 else None
            t0 = time.perf_counter()
            res = fe.query_batch(qs, tenant=names[i % len(names)],
                                 deadline_s=dl)
            dt = time.perf_counter() - t0
            my_lats.append(dt)
            my_q += len(res)
            if dl is not None and dt > dl:
                my_viol += 1
            my_sheds += sum(1 for r in res if r.engine == "shed")
        with lock:
            lats.extend(my_lats)
            viol += my_viol
            sheds += my_sheds
            total_q += my_q

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(level)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    arr = np.asarray(lats)
    return dict(
        qps=total_q / wall,
        p50_s=float(np.percentile(arr, 50)),
        p99_s=float(np.percentile(arr, 99)),
        violations=viol,
        sheds=sheds,
        wall_s=wall,
    )


def _bench(quick: bool) -> dict:
    import jax

    n = 2000 if quick else 6000
    k, tau = max(K_BUCKETS), 24
    calls_per_round = 64 if quick else 128
    rounds = 3 if quick else 5

    rt, arms, names = _build(n, k, tau)
    for fe in arms.values():  # coalesced first pays the process jit cache
        _warm(fe, names)

    # interleaved rounds, arm order alternating so neither arm always
    # rides the colder half of a scheduler burst
    per: dict[str, dict[int, list[dict]]] = {
        arm: {lv: [] for lv in LEVELS} for arm in arms
    }
    order = list(arms)
    for r in range(rounds):
        for lv in LEVELS:
            iters = max(2, calls_per_round // lv)
            for arm in (order if r % 2 == 0 else order[::-1]):
                per[arm][lv].append(_run_level(arms[arm], names, lv, iters))

    results: dict[str, dict] = {}
    for arm, by_level in per.items():
        results[arm] = {}
        for lv, rows in by_level.items():
            results[arm][str(lv)] = dict(
                qps=float(max(x["qps"] for x in rows)),
                p50_s=float(min(x["p50_s"] for x in rows)),
                p99_s=float(min(x["p99_s"] for x in rows)),
                p99_p50_ratio=float(
                    min(x["p99_s"] / x["p50_s"] for x in rows)),
                violations=int(min(x["violations"] for x in rows)),
                sheds=int(sum(x["sheds"] for x in rows)),
                rounds=[{k_: float(v) if isinstance(v, float) else v
                         for k_, v in x.items()} for x in rows],
            )
    speedup = {
        str(lv): results["coalesced"][str(lv)]["qps"]
        / results["percall"][str(lv)]["qps"]
        for lv in LEVELS
    }
    co_stats = arms["coalesced"].stats()
    co = co_stats.get("coalesce") or {}
    cm = co_stats.get("cost_model") or {}
    win = co.get("window") or {}
    trace = win.get("trace") or []
    t0_trace = trace[0][0] if trace else 0.0
    co_cfg = arms["coalesced"].coalescer.config
    dev = jax.devices()[0]
    out = dict(
        n=n, k=k, tau=tau,
        calls_per_round=calls_per_round,
        rounds=rounds,
        levels=list(LEVELS),
        k_buckets=list(K_BUCKETS),
        queries_per_call=[1, 2],
        tenant_count=len(names),
        deadline_s=DEADLINE_S,
        window=dict(
            adaptive=bool(co_cfg.adaptive),
            seed_us=float(co_cfg.window_s * 1e6),
            min_us=float(co_cfg.window_min_s * 1e6),
            max_us=float(co_cfg.window_max_s * 1e6),
        ),
        dispatchers=int(co.get("dispatchers", 0)),
        results=results,
        speedup={lv: float(s) for lv, s in speedup.items()},
        speedup_4=float(speedup["4"]),
        speedup_16=float(speedup["16"]),
        p99_p50_ratio_4=float(
            results["coalesced"]["4"]["p99_p50_ratio"]),
        deadline_violations=int(
            min(results[arm][str(lv)]["violations"]
                for arm in results for lv in LEVELS)),
        sheds=int(sum(results[arm][str(lv)]["sheds"]
                      for arm in results for lv in LEVELS)),
        coalesced_calls=int(co.get("coalesced_calls", 0)),
        coalesce_groups=int(co.get("groups", 0)),
        stacked_solves=int(co.get("stacked_solves", 0)),
        stacked_rows=int(co.get("stacked_rows", 0)),
        solo_calls=int(
            arms["coalesced"].registry.counter("serve.coalesce.solo").value),
        # the adaptive controller's window-size-over-time series
        # (seconds since first evaluation, window seconds) — uploaded
        # with the artifact so window dynamics are reviewable in CI
        window_trace=[
            [float(t - t0_trace), float(w)] for t, w in trace
        ],
        window_rate_hz=float(win.get("rate_hz") or 0.0),
        window_solve_est_s=win.get("solve_est_s"),
        cost_model_decisions=cm.get("decisions", [])[-8:],
        tenant_traffic=co_stats.get("tenant_traffic"),
        device_count=int(jax.device_count()),
        backend=str(jax.default_backend()),
        device_kind=str(getattr(dev, "device_kind", dev.platform)),
        machine=f"{_platform.system()}-{_platform.machine()}",
        host=_platform.node(),
    )
    for fe in arms.values():
        fe.close()
    rt.close()
    return out


def check(tolerance: float = 0.2, quick: bool = True) -> int:
    """Rerun the quick load bench and compare against the committed
    artifact; returns a process exit code (1 on failure). See the module
    docstring for the gate list."""
    if not os.path.exists(_JSON_PATH):
        print(f"check: no committed {_JSON_PATH}; nothing to compare")
        return 0
    with open(_JSON_PATH) as f:
        old = json.load(f)
    new = _bench(quick)
    with open(_JSON_PATH.replace(".json", ".check.json"), "w") as f:
        json.dump(new, f, indent=2)
    rc = 0
    # config drift always fails: a changed workload invalidates the
    # committed baseline, re-baseline with `frontend_load --quick --json`
    for key in ("n", "k", "tau", "calls_per_round", "levels", "k_buckets",
                "tenant_count", "window"):
        if key in old and old[key] != new[key]:
            print(f"check: CONFIG CHANGED: {key} "
                  f"(committed {old[key]!r} vs here {new[key]!r}); "
                  f"re-baseline with `frontend_load --quick --json`")
            rc = 1
    same_env = True
    for key in ("backend", "device_kind", "machine"):
        if key in old and old[key] != new[key]:
            print(f"check: note: {key} differs "
                  f"(committed {old[key]!r} vs here {new[key]!r})")
            same_env = False
    # the tentpole's headline number: the committed artifact must show
    # coalescing >= 2x at 16 clients, and the re-run must never measure
    # the merged path as slower than 16 solo dispatches (machine-relative
    # ratio, gated everywhere)
    committed = old.get("speedup_16", 0.0)
    ok = committed >= 2.0
    print(f"check: speedup_16 committed = {committed:.2f} (floor 2.00) -> "
          f"{'OK' if ok else 'BASELINE REGRESSION'}")
    if not ok:
        rc = 1
    # PR 10: moderate concurrency must be a win too — stacked
    # cross-tenant dispatch and the adaptive window bought speedup_4
    # above parity, and no re-baseline may give that back
    committed4 = old.get("speedup_4", 0.0)
    ok = committed4 > 1.0
    print(f"check: speedup_4 committed = {committed4:.2f} (floor 1.00, "
          f"strict) -> {'OK' if ok else 'BASELINE REGRESSION'}")
    if not ok:
        rc = 1
    print(f"check: speedup_4 here = {new['speedup_4']:.2f} "
          f"(report-only; stacked_solves={new['stacked_solves']}, "
          f"window_trace={len(new['window_trace'])} samples)")
    ok = new["speedup_16"] >= 1.0
    print(f"check: speedup_16 here = {new['speedup_16']:.2f} "
          f"(floor 1.00) -> {'OK' if ok else 'COALESCING REGRESSION'}")
    if not ok:
        rc = 1
    ratio = new["p99_p50_ratio_4"]
    ok = ratio <= 2.0
    print(f"check: p99_p50_ratio_4 = {ratio:.2f} (min over rounds, "
          f"ceiling 2.00) -> {'OK' if ok else 'TAIL REGRESSION'}")
    if not ok:
        rc = 1
    dv = new["deadline_violations"]
    ok = dv == 0
    print(f"check: deadline_violations = {dv} (min over rounds, must "
          f"be 0) -> {'OK' if ok else 'DEADLINE REGRESSION'}")
    if not ok:
        rc = 1
    if new["sheds"]:  # generous budgets: any shed is a routing bug
        print(f"check: sheds = {new['sheds']} (expected 0) -> "
              f"SHED REGRESSION")
        rc = 1
    ok = new["coalesced_calls"] > 0
    print(f"check: coalesced_calls = {new['coalesced_calls']} over "
          f"{new['coalesce_groups']} groups (solo "
          f"{new['solo_calls']}) -> {'OK' if ok else 'WINDOW DEAD'}")
    if not ok:
        rc = 1
    metric = "coalesced qps @16"
    old_q = old.get("results", {}).get("coalesced", {}).get("16", {})
    if "qps" in old_q:
        floor = old_q["qps"] * (1.0 - tolerance)
        got = new["results"]["coalesced"]["16"]["qps"]
        ok = got >= floor
        verdict = "OK" if ok else (
            "REGRESSION" if same_env
            else "BELOW FLOOR (env differs, not gated)"
        )
        print(f"check: {metric}: committed {old_q['qps']:.0f}, "
              f"now {got:.0f}, floor {floor:.0f} -> {verdict}")
        if not ok and same_env:
            rc = 1
    return rc


def main(quick: bool = False, emit_json: bool = False):
    r = _bench(quick)
    if emit_json:
        with open(_JSON_PATH, "w") as f:
            json.dump(r, f, indent=2)
    for lv in LEVELS:
        c = r["results"]["coalesced"][str(lv)]
        p = r["results"]["percall"][str(lv)]
        yield csv_line(
            f"frontend_load_{lv}c", 1e6 / c["qps"],
            f"qps={c['qps']:.0f} percall_qps={p['qps']:.0f} "
            f"speedup={r['speedup'][str(lv)]:.2f}x "
            f"p50={c['p50_s'] * 1e3:.2f}ms p99={c['p99_s'] * 1e3:.2f}ms")
    yield csv_line(
        "frontend_load_summary", 0.0,
        f"speedup4={r['speedup_4']:.2f}x "
        f"speedup16={r['speedup_16']:.2f}x "
        f"tail4={r['p99_p50_ratio_4']:.2f} "
        f"violations={r['deadline_violations']} "
        f"coalesced={r['coalesced_calls']}/{r['coalesce_groups']}groups "
        f"stacked={r['stacked_solves']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    if args.check:
        sys.exit(check(quick=True))
    for line in main(quick=args.quick, emit_json=args.json):
        print(line, flush=True)
