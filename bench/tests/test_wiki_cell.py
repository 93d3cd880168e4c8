"""The transversal query cell ``wiki-restored-query`` at a size a CPU test
run holds: a sound run reads ``correct``; a run whose timed path is broken
underneath does not (a dependent answer, an answer altered where it is
produced, an epoch that lost a row); the ``Precision.HIGH`` control fails
a limit; and the readers of the per-visit cost and the sweeps per launch,
on hand-made spans.

At 2,048 points the scan has not settled (a center keeps every page while
some topic is held fewer than k times), so the coreset holds nearly every
point, and the query sizes are cut to k <= 8 to keep the jit solves short
on the CPU."""
import numpy as np
import pytest

import cells
import harness
from control_transversal import control_numbers
from reference import transversal
from repro.obs import SpanRecord

NAME = "wiki-restored-query"
cells.SMALL.setdefault(NAME, {
    "mix.state.points": 2048, "mix.streams.0.rate_per_s": 4.0,
    "mix.streams.0.max_batch": 2,
    "mix.streams.0.classes": [
        {"share": 0.5, "k": [4, 8], "filter_share": 0.5, "filter_cats": 20},
        {"share": 0.5, "k": [6, 8]},
    ],
})
cells.SECONDS.setdefault(NAME, 1.5)


def _patch(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


def _answers(edit):
    """``edit(ctx, spec, loc) -> loc`` applied to every answer the jit
    engine produces."""
    from repro.core.solvers import jit_sum

    eng = jit_sum.JitSumBatchEngine
    orig = eng.solve_batch

    def solve_batch(self, ctx, specs):
        out = orig(self, ctx, specs)
        for s, sol in zip(specs, out):
            sol.local_indices = edit(ctx, s, np.array(sol.local_indices))
        return out
    return _patch(eng, "solve_batch", solve_batch)


def answer_made_dependent():
    """The answer's last two rows become two rows that hold one and the
    same single topic: no matching gives both a topic."""
    def edit(ctx, s, loc):
        cats = np.asarray(ctx.cats)
        single = (cats >= 0).sum(axis=1) == 1
        for t in np.unique(cats[single, 0]):
            pair = np.flatnonzero(single & (cats[:, 0] == t))
            pair = pair[~np.isin(pair, loc[:-2])]
            if loc.size >= 2 and pair.size >= 2:
                loc[-2:] = pair[:2]
                return loc
        return loc
    return _answers(edit)


def answer_altered_where_produced():
    """The answer's last row is swapped for the allowed row, outside it,
    that keeps it independent and adds the least diversity."""
    def edit(ctx, s, loc):
        if loc.size < 2:
            return loc
        cats = np.asarray(ctx.cats)
        oh = transversal.onehot(cats, ctx.spec.num_categories)
        ok, _ = transversal.swap_table(cats, oh, loc)
        cand = ok[:, -1] & s.allow_mask(ctx.size)
        cand[loc] = False
        if cand.any():
            D = np.asarray(ctx.D, np.float64)
            near = D[:, loc[:-1]].sum(axis=1)
            loc[-1] = int(np.argmin(np.where(cand, near, np.inf)))
        return loc
    return _answers(edit)


def epoch_drops_a_row():
    """The restored epoch lost one delegate the reference keeps."""
    from repro.serve.diversity import runtime

    load = runtime.load_checkpoint

    def altered(path):
        states, meta = load(path)
        st = states[0]
        dv = np.array(st.dv)
        live = np.argwhere(dv & np.array(st.cvalid)[:, None])
        dv[tuple(live[-1])] = False
        return [st._replace(dv=dv)] + states[1:], meta
    return _patch(runtime, "load_checkpoint", altered)


@pytest.mark.parametrize("fault", [
    answer_made_dependent,
    answer_altered_where_produced,
    epoch_drops_a_row,
])
def test_broken_path_is_not_correct(fault):
    _dep, _ran, numbers, correct = cells.run_small(NAME, 201, fault)
    assert not correct, numbers


def test_sound_run_is_correct():
    dep, ran, numbers, correct = cells.run_small(NAME, 202)
    assert correct, numbers
    served = [s for _k, _p, r in ran for s in r.served]
    assert served and all(s.result is not None for s in served)
    assert {s.result.engine for s in served} == {"jit_sum"}
    assert numbers["infeasible"] == 0 and numbers["epoch_mismatch"] == 0
    # the labels the checks read are the reference's full lists
    assert dep.catalog.cats.shape[1] == 3 and dep.catalog.caps is None


def test_control_fails_a_limit():
    dep, ran, _numbers, _correct = cells.run_small(NAME, 203)
    limits = cells.small_cell(NAME).limits
    ctl = control_numbers(dep, ran, 203, "high")
    assert any(v > limits[k] for k, v in ctl.items()), ctl


# -- the readers of the solve.run counters --------------------------------


def span(name, dur_us, **args):
    return SpanRecord(name=name, cat="query", trace_id="t", span_id=0,
                      parent_id=None, ts_us=0.0, dur_us=dur_us, tid=0,
                      args=args)


def read(metric, spans):
    return harness.load_module("metrics", metric).read(harness.Window(
        seconds=1.0, spans=spans, compiles=0, latencies_s=[0.3]))


SOLVES = [span("solve", 300_000.0, n=1), span("solve", 360_000.0, n=1)]
RUNS = [span("solve.run", 200_000.0, swaps=3, sweeps=2, visits=2 * 1000,
             matroid="transversal"),
        span("solve.run", 250_000.0, swaps=0, sweeps=1, visits=4 * 1000,
             matroid="transversal")]


@pytest.mark.parametrize("metric,want", [
    ("solve_sweeps_per_launch", 1.5),
    ("solve_run_us_per_visit", 450_000.0 / 6000),
])
def test_reads_the_run_counters(metric, want):
    assert read(metric, SOLVES + RUNS) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["solve_sweeps_per_launch",
                                    "solve_run_us_per_visit"])
@pytest.mark.parametrize("spans", [
    [],
    SOLVES,
    SOLVES + [span("solve.run", 200_000.0)],
], ids=["empty", "no_run_spans", "runs_without_counters"])
def test_a_program_without_the_counters_reads_nothing(metric, spans):
    assert read(metric, spans) is None


def test_per_visit_reads_nothing_without_visits():
    """The parent's program records ``sweeps`` but no ``visits``."""
    runs = [span("solve.run", 200_000.0, swaps=3, sweeps=2)]
    assert read("solve_run_us_per_visit", SOLVES + runs) is None
    assert read("solve_sweeps_per_launch", SOLVES + runs) == 1.0
