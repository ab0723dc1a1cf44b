#!/usr/bin/env python3
"""Control runs: the cell at its own size with a fault planted under the
timed path, and sound runs beside them, on several seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 101,102,103 \
        --seconds 10 --faults none,half_batch,unchanged_state,altered_answer

Prints one JSON line per seed and fault: whether the run came out
correct, and every number compared with its limit. The sound runs ("none")
give each number's lower reading, the faulted runs its upper one. Needs
the chips the cell asks for; the benchmark's own runs never plant a fault.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="none,half_batch", help="comma-separated; none = sound run")
    args = ap.parse_args(argv)

    from chipbench import entry, faults, harness

    try:
        op = entry.open_cell(args.workload)
    except entry.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    cell, mesh = op.cell, op.mesh
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in args.faults.split(","):
            t = time.perf_counter()
            try:
                with faults.plant(fault):
                    out = harness.execute(cell, seed, args.seconds, False, mesh, t,
                                          log=lambda m: None, work_dir=CHECKOUT / ".bench_work")
                rec = {"seed": seed, "fault": fault, "correct": out.correct, "checks": out.checks,
                       "metrics": out.metrics, "failed": out.failed}
            except Exception as e:  # a fault may also crash the run: that is a failed check
                rec = {"seed": seed, "fault": fault, "correct": False, "error": f"{type(e).__name__}: {e}"}
            rec["run_s"] = time.perf_counter() - t
            print(json.dumps(rec), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
