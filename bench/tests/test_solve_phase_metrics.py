"""The readers of the solve phase spans, on hand-made spans: two launches
of the program, and a window of a program that records no phases."""
import pytest

import harness
from repro.obs import SpanRecord

METRICS = ["solve_pack_ms_mean", "solve_h2d_ms_mean", "solve_run_ms_mean",
           "solve_value_ms_mean", "solve_h2d_mb_per_launch"]


def span(name, dur_us, **args):
    return SpanRecord(name=name, cat="query", trace_id="t", span_id=0,
                      parent_id=None, ts_us=0.0, dur_us=dur_us, tid=0,
                      args=args)


def window(spans):
    return harness.Window(seconds=1.0, spans=spans, compiles=0,
                          latencies_s=[0.3, 0.4])


TWO_LAUNCHES = [
    span("solve", 300_000.0, n=1), span("solve", 360_000.0, n=1),
    span("solve.pack", 1_000.0), span("solve.pack", 3_000.0),
    span("solve.h2d", 500.0, bytes=29_702_500),
    span("solve.h2d", 700.0, bytes=29_712_500),
    span("solve.run", 200_000.0), span("solve.run", 250_000.0),
    span("solve.value", 20_000.0, n=1), span("solve.value", 30_000.0, n=1),
]


@pytest.mark.parametrize("metric,want", [
    ("solve_pack_ms_mean", 2.0), ("solve_h2d_ms_mean", 0.6),
    ("solve_run_ms_mean", 225.0), ("solve_value_ms_mean", 25.0),
    ("solve_h2d_mb_per_launch", 29.7075),
])
def test_reads_the_phase_spans(metric, want):
    read = harness.load_module("metrics", metric).read
    assert read(window(TWO_LAUNCHES)) == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_phase_spans_reads_nothing(metric):
    read = harness.load_module("metrics", metric).read
    assert read(window([span("solve", 300_000.0, n=1)])) is None
    assert read(window([])) is None
