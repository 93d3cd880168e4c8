"""The reader of the local search's block-loop trips, on hand-made spans:
launches that carry ``trips`` on their ``solve.run`` spans, and windows of
a program that counts no trips."""
import pytest

import harness
from repro.obs import SpanRecord


def span(name, dur_us, **args):
    return SpanRecord(name=name, cat="query", trace_id="t", span_id=0,
                      parent_id=None, ts_us=0.0, dur_us=dur_us, tid=0,
                      args=args)


def window(spans):
    return harness.Window(seconds=1.0, spans=spans, compiles=0,
                          latencies_s=[0.3, 0.4])


def read(spans):
    return harness.load_module("metrics", "solve_trips_per_launch").read(
        window(spans))


SOLVES = [span("solve", 300_000.0, n=1), span("solve", 360_000.0, n=1)]


@pytest.mark.parametrize("trips,want", [
    ([40, 22], 31.0),
    ([11, 0], 5.5),
    ([7, 7], 7.0),
])
def test_reads_the_trips_over_the_solves(trips, want):
    spans = SOLVES + [span("solve.run", 2_000.0, swaps=3, sweeps=2, trips=t,
                           visits=5_450) for t in trips]
    assert read(spans) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    SOLVES,
    SOLVES + [span("solve.run", 80_000.0, swaps=3, sweeps=2, visits=5_450),
              span("solve.run", 90_000.0, swaps=1, sweeps=1, visits=2_725)],
    [span("solve.run", 2_000.0, swaps=3, sweeps=2, trips=30)],
], ids=["empty", "no_run_spans", "runs_without_trips", "no_solve_spans"])
def test_a_program_without_trip_counts_reads_nothing(spans):
    assert read(spans) is None
