"""The reader of the local search's swap counts, on hand-made spans: two
launches that carry ``swaps`` on their ``solve.run`` spans, and windows of a
program that counts no swaps."""
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
    return harness.load_module("metrics", "solve_swaps_per_launch").read(
        window(spans))


SOLVES = [span("solve", 300_000.0, n=1), span("solve", 360_000.0, n=1)]


@pytest.mark.parametrize("runs,want", [
    ([(3, 2), (0, 1)], 1.5),
    ([(12, 3), (9, 2)], 10.5),
    ([(0, 1), (0, 1)], 0.0),
])
def test_reads_the_swaps_over_the_solves(runs, want):
    spans = SOLVES + [span("solve.run", 200_000.0, swaps=s, sweeps=w)
                      for s, w in runs]
    assert read(spans) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    SOLVES,
    SOLVES + [span("solve.run", 200_000.0), span("solve.run", 250_000.0)],
    [span("solve.run", 200_000.0, swaps=3, sweeps=2)],
], ids=["empty", "no_run_spans", "runs_without_swaps", "no_solve_spans"])
def test_a_program_without_swap_counts_reads_nothing(spans):
    assert read(spans) is None
