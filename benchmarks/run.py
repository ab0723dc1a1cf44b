"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV lines and a validation summary of
the paper's qualitative claims. Tables map to the paper as:

    fig3_*    Fig 3   ingest scaling vs clients x servers (+ saturation)
    fig4_*    Fig 4   backpressure regimes (rate variance)
    table1_*  Table I query responsiveness (time-to-first-result)
    table1_concurrency_*  (ours) first-result latency vs concurrent
              sessions over the serve plane, at rest and under live ingest
    table2_*  Table II query total runtime
    kernel_*  (ours)  store kernel throughput

Every benchmarks/bench_*.py module is wired through this harness — CSV
lines, validate() failures (where the module defines them), and a
checked-in BENCH_<name>.json artifact (common.write_artifact) per
module. None are manual-only.
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller datasets (CI-sized)")
    ap.add_argument("--rows", type=int, default=None, help="bench store size")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    from . import (
        bench_ingest_scaling,
        bench_kernels,
        bench_query_concurrency,
        bench_query_responsiveness,
        bench_query_runtime,
    )
    from .common import build_bench_store, write_artifact

    lines = []
    failures = []

    print("# building bench store ...", file=sys.stderr, flush=True)
    n_rows = args.rows or (30_000 if args.quick else 120_000)
    bs = build_bench_store(n_rows=n_rows)

    print("# table I / fig 5: query responsiveness ...", file=sys.stderr, flush=True)
    r1 = bench_query_responsiveness.run(bs)
    lines += bench_query_responsiveness.emit_csv(r1)
    failures += [f"responsiveness: {f}" for f in bench_query_responsiveness.validate(r1)]
    print("# wrote", write_artifact("query_responsiveness",
                                    bench_query_responsiveness.emit_json(r1)),
          file=sys.stderr, flush=True)

    print("# table II: query runtime ...", file=sys.stderr, flush=True)
    r2 = bench_query_runtime.run(bs)
    lines += bench_query_runtime.emit_csv(r2)
    failures += [f"runtime: {f}" for f in bench_query_runtime.validate(r2)]
    # Canonical checked-in artifacts (benchmarks/BENCH_*.json, one shared
    # emitter in common.py): regenerated on every harness run so
    # re-anchors can track the perf trajectory (docs/benchmarks.md).
    print("# wrote", write_artifact("query_runtime", bench_query_runtime.emit_json(r2)),
          file=sys.stderr, flush=True)

    print("# fig 3/4: ingest scaling + backpressure ...", file=sys.stderr, flush=True)
    r3 = bench_ingest_scaling.run(quick=args.quick)
    lines += bench_ingest_scaling.emit_csv(r3)
    failures += [f"ingest: {f}" for f in bench_ingest_scaling.validate(r3)]
    print("# wrote", write_artifact("ingest_scaling", bench_ingest_scaling.emit_json(r3)),
          file=sys.stderr, flush=True)

    print("# serve plane: latency vs concurrent sessions ...", file=sys.stderr, flush=True)
    r5 = bench_query_concurrency.run(quick=args.quick)
    lines += bench_query_concurrency.emit_csv(r5)
    failures += [f"concurrency: {f}" for f in bench_query_concurrency.validate(r5)]
    print("# wrote", write_artifact("query_concurrency", bench_query_concurrency.emit_json(r5)),
          file=sys.stderr, flush=True)

    print("# kernels ...", file=sys.stderr, flush=True)
    r4 = bench_kernels.run()
    lines += bench_kernels.emit_csv(r4)
    print("# wrote", write_artifact("kernels", bench_kernels.emit_json(r4)),
          file=sys.stderr, flush=True)

    print("name,us_per_call,derived")
    for line in lines:
        print(line)

    print(f"\n# paper-claim validation: {len(failures)} failure(s)", file=sys.stderr)
    for f in failures:
        print(f"#   FAIL {f}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
