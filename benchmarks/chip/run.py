#!/usr/bin/env python3
"""Chip benchmark of the device event store: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in BENCHMARK.json at the root of the checkout; its
configuration, traffic mix and metrics are files of their own under this
directory (see chipbench/harness.py). The run builds its data from
--seed, warms every shape the cell uses (set-up), measures for --seconds,
checks every result against the numpy reference, and prints as its last
line of standard output one JSON object:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"], "checks"}

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 they are its per-layer metrics, read from a profiler trace of
the window and the program's spans and counters. The numbers compared
with the reference are also the last lines of standard error, each with
its limit. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def _fail(msg: str) -> int:
    print(f"benchmarks/chip/run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import entry, harness

    try:
        op = entry.open_cell(args.workload)
    except entry.NoChip as e:
        return _fail(str(e))
    except (harness.BenchError, ImportError, OSError, KeyError, ValueError) as e:
        return _fail(f"cannot resolve workload {args.workload!r}: {e}")
    cell, mesh = op.cell, op.mesh
    print(f"cell: {args.workload} seed={args.seed} seconds={args.seconds} trace={args.trace}", flush=True)
    print(f"device: {op.kind} x{cell.chips}, compile cache {op.cache_dir}", flush=True)
    try:
        out = harness.execute(cell, args.seed, args.seconds, bool(args.trace), mesh, T_PROCESS,
                              log=lambda m: print(m, flush=True),
                              work_dir=CHECKOUT / ".bench_work")
    except Exception:
        traceback.print_exc()
        return _fail("FAILED")
    hbm = op.hbm_bytes
    print(f"memory peak: {out.device['memory_peak_bytes']} bytes, "
          f"{100 * out.device['memory_peak_bytes'] / hbm}% of {hbm}", flush=True)
    for k, v in out.notes.items():
        print(f"{k}: {v}", flush=True)
    for line in harness.check_lines(out):
        print(line, file=sys.stderr, flush=True)
    print(harness.result_line(out, bool(args.trace)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
