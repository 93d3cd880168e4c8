"""Benchmark harness: one entry per paper table/figure + kernel microbench.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
    PYTHONPATH=src python -m benchmarks.run --check

``--check`` is the serving-perf regression gate: it reruns
``serve_bench --quick`` and ``frontend_load --quick`` and exits 1 if
``ingest_points_per_s`` / ``batched_qps`` regressed more than 20%
against the committed ``BENCH_serve.json``, or any query-path gate
fails against ``BENCH_frontend.json`` (coalescing speedup, tail ratio,
deadline violations — see ``frontend_load``'s docstring).

Prints ``name,us_per_call,derived`` CSV (paper analogues documented in each
module; DESIGN.md §9 maps benchmarks -> paper figures).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--check", action="store_true",
                    help="rerun serve_bench --quick + frontend_load "
                         "--quick and fail on regressions vs the "
                         "committed BENCH_serve.json / "
                         "BENCH_frontend.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.check:
        from . import frontend_load, serve_bench

        rc = serve_bench.check()
        rc = frontend_load.check() or rc
        sys.exit(rc)

    from . import (
        coreset_sizes,
        fig1_seq_vs_amt,
        fig2_streaming,
        fig3_mapreduce,
        frontend_load,
        kernel_bench,
        roofline_report,
        serve_bench,
        variants_quality,
    )

    suites = [
        ("kernels", kernel_bench.main),
        ("variants", variants_quality.main),
        ("coreset_sizes", coreset_sizes.main),
        ("fig1", fig1_seq_vs_amt.main),
        ("fig2", fig2_streaming.main),
        ("fig3", fig3_mapreduce.main),
        ("serve", serve_bench.main),
        ("frontend_load", frontend_load.main),
        ("roofline", roofline_report.main),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites:
        if args.only and args.only != name:
            continue
        try:
            for line in fn(quick=args.quick):
                print(line, flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
