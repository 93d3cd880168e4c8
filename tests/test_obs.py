"""Contracts of the ``repro.obs`` observability layer and its integration
with the serving stack: registry semantics, log-bucket histogram geometry,
the tracer-leak guard, trace-ID propagation (including across the async
ingest worker's thread), staleness/publish-latency accounting under
concurrent submit+flush, recompile-counter exactness at pow-2 bucket
boundaries, the on_publish error containment fix, and the CacheStats
back-compat surface.
"""
import json
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.matroid import MatroidSpec
from repro.obs.metrics import bucket_index, bucket_lo
from repro.serve.diversity import (
    DiversityQuery,
    QueryFrontend,
    StreamRuntime,
)
from repro.serve.diversity.cache import CacheStats, DistanceCache

SPEC = MatroidSpec("partition", num_categories=4, gamma=1)
CAPS = np.full(4, 4, np.int32)


def make_runtime(**kw):
    kw.setdefault("registry", obs.MetricsRegistry())
    return StreamRuntime(SPEC, 8, tau=16, caps=CAPS, **kw)


def feed(rng, n=64):
    return (
        rng.normal(size=(n, 4)).astype(np.float32),
        rng.integers(0, 4, size=(n, 1)).astype(np.int32),
    )


# ---------------------------------------------------------------------------
# registry + histogram geometry
# ---------------------------------------------------------------------------


def test_registry_series_identity_and_labels():
    reg = obs.MetricsRegistry()
    a = reg.counter("req", tenant="a")
    b = reg.counter("req", tenant="b")
    assert a is reg.counter("req", tenant="a")  # get-or-create
    assert a is not b
    a.inc(3)
    b.inc()
    snap = reg.snapshot()
    assert snap["req{tenant=a}"]["value"] == 3
    assert snap["req{tenant=b}"]["value"] == 1
    # label order never matters
    c = reg.gauge("g", x="1", y="2")
    assert c is reg.gauge("g", y="2", x="1")
    # same series name under a different instrument kind is a loud error
    with pytest.raises(TypeError):
        reg.histogram("req", tenant="a")


def test_histogram_log2_bucket_boundaries():
    # buckets are keyed off the frexp exponent: bucket i holds
    # [2^(i-30), 2^(i-29)) (since 1e-9 ~ 1.074 * 2^-30), so the edges sit
    # exactly at powers of two — one ulp below an edge is the previous
    # bucket, and bucket_lo(i) = 1e-9 * 2^i always lands inside bucket i
    for i in (1, 5, 30, 60):
        edge = 2.0 ** (i - 30)
        assert bucket_index(edge) == i
        assert bucket_index(np.nextafter(edge, 0.0)) == i - 1
        assert bucket_index(bucket_lo(i)) == i
        assert bucket_lo(i) / bucket_lo(i - 1) == 2.0
    # monotone in v across four decades
    idx = [bucket_index(1e-8 * 1.9 ** j) for j in range(16)]
    assert idx == sorted(idx)
    # clamps: tiny to bucket 0, absurd to the last bucket — never a throw,
    # never an allocation
    assert bucket_index(0.0) == 0
    assert bucket_index(-1.0) == 0
    assert bucket_index(1e30) == 95


def test_histogram_quantiles_within_bucket_resolution():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat")
    vals = [0.001 * (1 + i % 7) for i in range(1000)]
    for v in vals:
        h.observe(v)
    d = h.describe()
    assert d["count"] == 1000
    assert d["min"] == pytest.approx(min(vals))
    assert d["max"] == pytest.approx(max(vals))
    assert d["sum"] == pytest.approx(sum(vals))
    # log2 buckets: a quantile is off by at most 2x, clamped to [min, max]
    for q, true in ((0.5, np.quantile(vals, 0.5)),
                    (0.95, np.quantile(vals, 0.95))):
        got = h.quantile(q)
        assert true / 2 <= got <= true * 2
        assert d["min"] <= got <= d["max"]
    # single observation reports itself exactly (clamp to min == max)
    h1 = reg.histogram("one")
    h1.observe(0.0042)
    assert h1.quantile(0.5) == pytest.approx(0.0042)


def test_registry_reset_and_disable():
    reg = obs.MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("h")
    c.inc(5)
    h.observe(1.0)
    reg.reset()
    assert c.value == 0 and h.count == 0
    assert reg.counter("n") is c  # handles survive reset
    reg.enabled = False
    c.inc()
    h.observe(1.0)
    assert c.value == 0 and h.count == 0  # disabled ops are no-ops


def test_write_jsonl(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("a", engine="x").inc(2)
    reg.histogram("b").observe(0.5)
    p = tmp_path / "metrics.jsonl"
    reg.write_jsonl(str(p))
    recs = [json.loads(line) for line in p.read_text().splitlines()]
    by_series = {r["series"]: r for r in recs}
    assert by_series["a{engine=x}"]["value"] == 2
    assert by_series["a{engine=x}"]["labels"] == {"engine": "x"}
    assert by_series["b"]["count"] == 1


# ---------------------------------------------------------------------------
# tracer-leak guard
# ---------------------------------------------------------------------------


def test_metric_mutation_inside_jit_trace_raises():
    reg = obs.MetricsRegistry()
    c = reg.counter("leaked")
    h = reg.histogram("leaked_h")

    @jax.jit
    def f(x):
        c.inc()
        return x * 2

    with pytest.raises(obs.TracerLeakError):
        f(jnp.ones(3))
    assert c.value == 0  # the trace-time call never landed

    @jax.jit
    def g(x):
        h.observe(0.1)
        return x

    with pytest.raises(obs.TracerLeakError):
        g(jnp.ones(3))


def test_span_inside_jit_trace_raises():
    buf = obs.TraceBuffer(capacity=16)

    @jax.jit
    def f(x):
        with buf.span("inside"):
            return x + 1

    with pytest.raises(obs.TracerLeakError):
        f(jnp.ones(3))
    assert buf.drain() == []


def test_guard_is_thread_local():
    # the ingest worker mutating metrics while ANOTHER thread is tracing
    # must not trip the guard: jax trace state is thread-local
    reg = obs.MetricsRegistry()
    c = reg.counter("worker_side")
    errs = []
    go = threading.Event()
    done = threading.Event()

    def worker():
        go.wait(5.0)
        try:
            c.inc()
        except Exception as e:  # pragma: no cover - the failure mode
            errs.append(e)
        done.set()

    th = threading.Thread(target=worker, daemon=True)
    th.start()

    @jax.jit
    def f(x):
        go.set()
        done.wait(5.0)  # worker increments WHILE this trace is active
        return x

    f(jnp.ones(2))
    th.join(5.0)
    assert not errs and c.value == 1


def test_instrumented_serving_paths_are_trace_clean(rng):
    # end-to-end: ingest + query through every instrumented layer raises
    # no TracerLeakError (i.e. no host-side obs call leaked into a trace)
    rt = make_runtime()
    fe = QueryFrontend(rt)
    P, C = feed(rng, 128)
    rt.ingest(P, C)
    res = fe.query_batch([DiversityQuery(k=4)])
    assert len(res) == 1


# ---------------------------------------------------------------------------
# tracing: spans, IDs, export
# ---------------------------------------------------------------------------


def test_trace_id_propagates_through_query_batch_spans(rng):
    rt = make_runtime()
    fe = QueryFrontend(rt)
    P, C = feed(rng, 128)
    rt.ingest(P, C)
    buf = obs.default_buffer()
    buf.clear()
    # jit_sum: the engine whose launch records the solve phase spans
    fe.query_batch([DiversityQuery(k=4), DiversityQuery(k=3)],
                   engine="jit_sum")
    spans = buf.drain()
    names = {s.name for s in spans}
    assert {"query_batch", "resolve_tenant", "acquire_epoch",
            "cache_entry", "solve", "solve.pack", "solve.h2d", "solve.run",
            "solve.value"} <= names
    ids = {s.trace_id for s in spans}
    assert len(ids) == 1 and None not in ids  # one request, one trace
    # a second request gets a DIFFERENT trace id
    buf.clear()
    fe.query_batch([DiversityQuery(k=4)])
    ids2 = {s.trace_id for s in buf.drain()}
    assert len(ids2) == 1 and ids2 != ids


def test_trace_id_crosses_submit_to_worker_thread(rng):
    rt = make_runtime()
    P, C = feed(rng, 64)
    buf = obs.default_buffer()
    buf.clear()
    rt.submit(P, C)
    rt.flush()
    spans = buf.drain()
    sub = [s for s in spans if s.name == "submit"]
    wrk = [s for s in spans if s.name == "worker_ingest"]
    assert len(sub) == 1 and len(wrk) == 1
    assert sub[0].trace_id is not None
    assert wrk[0].trace_id == sub[0].trace_id  # resumed across threads
    assert wrk[0].tid != sub[0].tid  # ...on a genuinely different thread
    rt.close()


def test_chrome_trace_export(tmp_path):
    buf = obs.TraceBuffer(capacity=8)
    with buf.span("outer", cat="test", n=3):
        with buf.span("inner", cat="test"):
            pass
    p = tmp_path / "trace.json"
    buf.dump(str(p))
    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["outer", "inner"]
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0 and "pid" in e
    # spans record on exit, so the outer span's window covers the inner's
    outer, inner = evs
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert evs[0]["args"]["n"] == 3


def test_ring_buffer_overwrites_oldest():
    buf = obs.TraceBuffer(capacity=4)
    for i in range(10):
        with buf.span(f"s{i}"):
            pass
    got = [s.name for s in buf.drain()]
    assert got == ["s6", "s7", "s8", "s9"]  # newest capacity survive


def test_span_tree_links_children_to_parents():
    buf = obs.TraceBuffer(capacity=16)
    with obs.trace():
        with buf.span("outer"):
            with buf.span("a"):
                pass
            with buf.span("b"):
                with buf.span("b1"):
                    pass
            with obs.trace():  # joins the open trace, under "outer"
                with buf.span("joined"):
                    pass
    with obs.trace():
        with buf.span("next"):
            pass
    with buf.span("host"):
        with obs.trace():
            with buf.span("fresh"):
                pass
        with obs.resume_trace("feedfacefeedface"):
            with buf.span("resumed"):
                pass
        with buf.span("after"):
            pass
    by = {s.name: s for s in buf.drain()}
    assert len({s.span_id for s in by.values()}) == len(by)
    outer = by["outer"]
    assert outer.parent_id is None
    assert by["a"].parent_id == by["b"].parent_id == outer.span_id
    assert by["b1"].parent_id == by["b"].span_id
    assert by["joined"].parent_id == outer.span_id
    assert by["joined"].trace_id == outer.trace_id
    # a new trace starts a new root; so does a resumed one
    assert by["next"].parent_id is None
    assert by["next"].trace_id != outer.trace_id
    assert by["fresh"].parent_id is None and by["resumed"].parent_id is None
    assert by["resumed"].trace_id == "feedfacefeedface"
    # ...and leaving them restores the enclosing parent
    assert by["after"].parent_id == by["host"].span_id
    evs = {e["name"]: e for e in buf.chrome_trace()["traceEvents"]}
    for name, rec in by.items():
        assert evs[name]["args"]["span_id"] == rec.span_id
        assert evs[name]["args"]["parent_id"] == rec.parent_id


def _phases_under_solve(spans):
    """{phase name: record} of the one launch under the one ``solve``
    span, after checking each phase is a child of it."""
    solve = [s for s in spans if s.name == "solve"]
    assert len(solve) == 1
    phases = {s.name: s for s in spans if s.name.startswith("solve.")}
    assert set(phases) == {"solve.pack", "solve.h2d", "solve.run",
                           "solve.value"}
    for ph in phases.values():
        assert ph.parent_id == solve[0].span_id
        assert ph.trace_id == solve[0].trace_id
    order = sorted(phases.values(), key=lambda s: s.ts_us)
    assert [s.name for s in order] == ["solve.pack", "solve.h2d",
                                       "solve.run", "solve.value"]
    return phases


def test_jit_sum_launch_records_phase_spans_under_solve(rng):
    rt = make_runtime()
    fe = QueryFrontend(rt)
    P, C = feed(rng, 128)
    rt.ingest(P, C)
    buf = obs.default_buffer()
    buf.clear()
    fe.query_batch([DiversityQuery(k=4), DiversityQuery(k=3)],
                   engine="jit_sum")
    ph = _phases_under_solve(buf.drain())
    B, kmax, m = (ph["solve.pack"].args[a] for a in ("B", "kmax", "m"))
    assert (B, kmax) == (2, 4)
    assert ph["solve.value"].args["n"] == 2
    h = SPEC.num_categories
    # D (float32), cats (m,) and caps (B, h) int32, allow (B, m) bool,
    # ks int32 and gammas float32 (B,)
    assert ph["solve.h2d"].args["bytes"] == (
        m * m * 4 + m * 4 + B * h * 4 + B * m + B * 4 + B * 4)


def _launch_partition(rng, m):
    from repro.core.solvers import SolveContext, SolveSpec
    from repro.core.solvers.jit_sum import JitSumBatchEngine

    ctx = SolveContext(
        D=rng.random((m, m)).astype(np.float32), spec=SPEC,
        cats=rng.integers(0, 4, (m, 1)).astype(np.int32), caps=CAPS)
    JitSumBatchEngine().solve_batch(ctx, [SolveSpec(k=3)] * 3)
    # B 4 (pow-2 of 3): D, cats (m,), caps (4, 4), allow, ks, gammas
    return m * m * 4 + m * 4 + 4 * 4 * 4 + 4 * m + 4 * 4 + 4 * 4


def _launch_transversal(rng, m):
    from repro.core.solvers import SolveContext, SolveSpec
    from repro.core.solvers.jit_sum import JitSumBatchEngine

    cats = np.full((m, 2), -1, np.int32)
    cats[:, 0] = rng.integers(0, 4, m)
    ctx = SolveContext(
        D=rng.random((m, m)).astype(np.float32),
        spec=MatroidSpec("transversal", num_categories=4, gamma=2),
        cats=cats)
    JitSumBatchEngine().solve_batch(ctx, [SolveSpec(k=3)])
    # B 1: D, the (m, 4) bool incidence, allow, ks, gammas
    return m * m * 4 + m * 4 + m + 4 + 4


def _launch_stacked(rng, m):
    from repro.core.solvers import SolveContext, SolveSpec
    from repro.core.solvers.stacked import solve_stacked

    lanes = [(SolveContext(
        D=rng.random((m, m)).astype(np.float32), spec=SPEC,
        cats=rng.integers(0, 4, (m, 1)).astype(np.int32), caps=CAPS),
        [SolveSpec(k=2)] * b) for b in (1, 2, 1)]
    solve_stacked(lanes)
    # T 4, B 2: Ds (4, m, m), cats (4, m), caps (4, 2, 4), allow (4, 2, m),
    # ks and gammas (4, 2)
    return (4 * m * m * 4 + 4 * m * 4 + 4 * 2 * 4 * 4 + 4 * 2 * m
            + 4 * 2 * 4 + 4 * 2 * 4)


@pytest.mark.parametrize("launch", [_launch_partition, _launch_transversal,
                                    _launch_stacked],
                         ids=["partition", "transversal", "stacked"])
def test_every_jit_launch_path_records_its_phases(rng, launch):
    buf = obs.default_buffer()
    buf.clear()
    with obs.trace(), obs.span("solve", cat="query"):
        want = launch(rng, 24)
    ph = _phases_under_solve(buf.drain())
    assert ph["solve.h2d"].args["bytes"] == want
    run = ph["solve.run"].args
    assert set(run) == {"swaps", "sweeps", "trips", "visits", "matroid"}
    # every row of the launch visits all 24 candidates in each sweep
    assert run["visits"] > 0 and run["visits"] % (24 * run["sweeps"]) == 0
    # the block loop makes at least one trip a sweep, at most one a visit
    assert run["sweeps"] <= run["trips"] <= run["visits"]
    assert run["matroid"] == ("transversal" if launch is _launch_transversal
                              else "partition")


def test_spans_land_nested_on_the_profiler_host_plane(rng, tmp_path):
    from jax.profiler import ProfileData

    rt = make_runtime()
    fe = QueryFrontend(rt)
    P, C = feed(rng, 128)
    rt.ingest(P, C)
    q = [DiversityQuery(k=4)]
    fe.query_batch(q, engine="jit_sum")  # compile outside the trace
    buf = obs.default_buffer()
    buf.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # the harness's anchor: the window annotation's start, stamped on
        # the span clock
        with jax.profiler.TraceAnnotation("bench/window"):
            anchor_us = obs.tracing.now_us()
            fe.query_batch(q, engine="jit_sum")
    finally:
        jax.profiler.stop_trace()
    ring = buf.drain()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host")
            for line in p.lines for ev in line.events]
    (w0,) = [a for name, a, _b in host if name == "bench/window"]
    off_ns = w0 - anchor_us * 1e3
    names = ["query_batch", "solve", "solve.pack", "solve.h2d",
             "solve.run", "solve.value"]
    got = {}
    for name in names:
        (rec,) = [s for s in ring if s.name == name]
        (ev,) = [e for e in host if e[0] == name]
        # the ring and the trace file agree on the span's start
        assert abs(ev[1] - (rec.ts_us * 1e3 + off_ns)) < 1e6, name
        got[name] = ev
    # nested as in the ring: each phase inside solve, solve inside the batch
    for child, parent in [("solve", "query_batch")] + [
            (n, "solve") for n in names[2:]]:
        assert got[parent][1] <= got[child][1]
        assert got[child][2] <= got[parent][2]


# ---------------------------------------------------------------------------
# recompile watch: exactness at pow-2 bucket boundaries
# ---------------------------------------------------------------------------


def test_recompile_counter_exact_across_pow2_buckets():
    from repro.core.solvers.jit_sum import bucket_pow2

    watch = obs.RecompileWatch()
    try:
        @jax.jit
        def f(x):
            return jnp.sum(x * 2.0)

        def call(n):
            b = bucket_pow2(n)
            x = jnp.zeros((b,), jnp.float32)  # OUTSIDE the region: array
            # creation may itself compile helpers; only f's compile may
            # be attributed to the bucket key
            with obs.compile_region(f"test[b={b}]"):
                f(x).block_until_ready()
            return b

        watch.reset()
        # 5, 6, 8 share the pow-2 bucket 8: exactly ONE compile
        for n in (5, 6, 8):
            assert call(n) == 8
        assert watch.by_key().get("test[b=8]", 0) == 1
        # 9 crosses the boundary into bucket 16: exactly one more
        assert call(9) == 16
        assert watch.by_key().get("test[b=16]", 0) == 1
        # re-crossing back re-uses the cached executable: no new events
        before = watch.total()
        call(7)
        call(16)
        assert watch.total() == before
        assert watch.by_key().get("test[b=8]", 0) == 1
        assert watch.by_key().get("test[b=16]", 0) == 1
    finally:
        watch.close()


def test_recompile_watch_windows_and_unattributed():
    watch = obs.RecompileWatch()
    try:
        @jax.jit
        def g(x):
            return x + 1

        x = jnp.zeros(3)  # created OUTSIDE any region: helper compiles
        # (zeros fill etc.) must not be attributed to win[a]
        with obs.compile_region("win[a]"):
            g(x).block_until_ready()
        assert watch.by_key().get("win[a]") == 1
        assert watch.seconds_by_key()["win[a]"] > 0
        watch.reset()  # a fresh measurement window
        with obs.compile_region("win[a]"):
            g(x).block_until_ready()  # cached: no event
        assert watch.total() == 0

        @jax.jit
        def h(x):
            return x - 1

        h(x).block_until_ready()  # no active region
        assert watch.by_key().get(obs.UNATTRIBUTED, 0) >= 1
        assert watch.total(include_unattributed=False) == 0
    finally:
        watch.close()


# ---------------------------------------------------------------------------
# serving integration: staleness, publish latency, worker containment
# ---------------------------------------------------------------------------


def test_staleness_and_publish_latency_under_concurrent_submit(rng):
    reg = obs.MetricsRegistry()
    rt = make_runtime(registry=reg, publish_every=2)
    P, C = feed(rng, 64)
    rt.ingest(P, C)  # init + compile off the measured path
    n_batches = 12
    threads = [
        threading.Thread(
            target=lambda i=i: rt.submit(*feed(np.random.default_rng(i), 32)),
            daemon=True,
        )
        for i in range(n_batches)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30.0)
    rt.flush()
    stale = reg.histogram("serve.epoch.staleness_s")
    pub = reg.histogram("serve.epoch.publish_latency_s")
    # every worker-ingested batch lands in the staleness histogram exactly
    # once (publish time - submit time), regardless of publish cadence
    assert stale.count == n_batches
    assert stale.sum >= 0 and math.isfinite(stale.sum)
    assert pub.count == reg.counter("serve.epoch.published").value > 0
    assert reg.counter("serve.submit.batches").value == n_batches
    assert reg.counter("serve.worker.errors").value == 0
    d = stale.describe()
    assert d["min"] >= 0 and d["p95"] >= d["min"]
    rt.close()


def test_on_publish_error_is_counted_not_fatal(rng):
    reg = obs.MetricsRegistry()
    boom = []

    def bad_callback(snap):
        boom.append(snap.epoch)
        raise RuntimeError("subscriber bug")

    rt = make_runtime(registry=reg, on_publish=bad_callback)
    P, C = feed(rng, 64)
    rt.submit(P, C)
    epoch = rt.flush()  # must NOT raise, must NOT kill the worker
    assert epoch >= 1 and boom
    errs = reg.counter("serve.publish.callback_errors").value
    assert errs == len(boom) > 0
    # the stream did not truncate: later submits still ingest
    n0 = rt.n_offered
    rt.submit(P, C)
    rt.flush()
    assert rt.n_offered == n0 + 64
    assert reg.counter("serve.worker.errors").value == 0
    rt.close()


def test_ingest_errors_still_truncate_the_stream(rng):
    # containment is for SUBSCRIBER bugs only: a real ingest failure must
    # keep surfacing on the next submit/flush (pinned by test_freshness)
    rt = make_runtime()
    P, C = feed(rng, 64)
    rt.submit(P, C)
    rt.flush()
    rt.submit(np.full((8, 3), 1.0, np.float32), None)  # wrong dim: fails
    with pytest.raises(RuntimeError, match="worker failed"):
        rt.flush()
    rt.close()


def test_query_metrics_labeled_by_tenant_and_engine(rng):
    reg = obs.MetricsRegistry()
    rt = make_runtime(registry=reg)
    fe = QueryFrontend(rt)
    P, C = feed(rng, 128)
    rt.ingest(P, C)
    fe.register_tenant("cosine", metric="cosine")
    fe.query_batch([DiversityQuery(k=4)] * 3)
    fe.query_batch([DiversityQuery(k=4)], tenant="cosine")
    snap = reg.snapshot()
    assert snap["serve.query.latency_s{tenant=default}"]["count"] == 1
    assert snap["serve.query.latency_s{tenant=cosine}"]["count"] == 1
    assert snap["serve.query.batch_size{tenant=default}"]["max"] == 3
    solve_keys = [
        key for key in snap
        if key.startswith("serve.solve.latency_s{")
        and snap[key]["count"] > 0
    ]
    assert any("engine=" in key and "tenant=default" in key
               for key in solve_keys)
    assert reg.counter(
        "serve.query.cache_misses", tenant="default"
    ).value == 1  # one entry build per (tenant, epoch), not per query
    # a second default batch over the unchanged epoch hits the warm entry
    fe.query_batch([DiversityQuery(k=4)])
    assert reg.counter(
        "serve.query.cache_hits", tenant="default"
    ).value == 1
    assert reg.counter(
        "serve.query.cache_misses", tenant="default"
    ).value == 1
    rt.close()


def test_stats_backcompat_view_still_works(rng):
    rt = make_runtime()
    fe = QueryFrontend(rt)
    P, C = feed(rng, 128)
    rt.ingest(P, C)
    fe.query(DiversityQuery(k=4))
    s = fe.stats()
    assert s["epoch"] >= 1
    assert s["cache"]["builds"] == 1 and s["cache"]["misses"] == 1
    fe.query(DiversityQuery(k=4))
    assert fe.stats()["cache"]["hits"] == 1
    rt.close()


# ---------------------------------------------------------------------------
# CacheStats back-compat
# ---------------------------------------------------------------------------


def test_cache_stats_registry_backed_backcompat():
    reg = obs.MetricsRegistry()
    s = CacheStats(reg, cache="t0")
    assert s.hits == 0 and s.misses == 0
    s.incr("hits")
    s.incr("builds", 2)
    assert s.hits == 1 and s.builds == 2  # plain-int attribute reads
    snap = s.snapshot()
    assert snap == {
        "hits": 1, "misses": 0, "builds": 2, "invalidations": 0,
        "evictions": 0, "expirations": 0, "sweeps": 0,
    }
    # and the same counts are visible as first-class registry series
    assert reg.snapshot()["serve.cache.builds{cache=t0}"]["value"] == 2
    with pytest.raises(AttributeError):
        s.nonexistent_field


def test_distance_cache_counts_in_isolated_registry():
    reg = obs.MetricsRegistry()
    cache = DistanceCache(registry=reg)
    key = ("spec", 1, "euclidean")
    assert cache.lookup(key, 7) is None
    pts = np.random.default_rng(0).normal(size=(6, 3)).astype(np.float32)
    cats = np.zeros((6, 1), np.int32)
    src = np.arange(6)
    cache.build(key, pts, cats, src, 7)
    assert cache.lookup(key, 7) is not None
    assert cache.stats.misses == 1
    assert cache.stats.builds == 1
    assert cache.stats.hits == 1
    # two caches over one registry never share series (cache=cN label)
    other = DistanceCache(registry=reg)
    assert other.stats.misses == 0


# ---------------------------------------------------------------------------
# module-level conveniences
# ---------------------------------------------------------------------------


def test_set_enabled_toggles_default_registry_and_buffer():
    obs.set_enabled(False)
    try:
        c = obs.counter("toggle_test")
        v0 = c.value
        c.inc()
        assert c.value == v0  # disabled
        buf = obs.default_buffer()
        n0 = len(buf.drain())
        with obs.span("toggle_span"):
            pass
        assert len(buf.drain()) == n0
    finally:
        obs.set_enabled(True)
    c = obs.counter("toggle_test")
    c.inc()
    assert c.value >= 1


def test_observability_report_shape():
    rep = obs.observability_report(obs.MetricsRegistry())
    assert set(rep) == {
        "metrics", "recompiles_by_key", "recompile_seconds_by_key"
    }
