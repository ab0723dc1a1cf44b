#!/usr/bin/env python3
"""Find the highest query rate the serve plane sustains: the cell's own
set-up (harness.prepare: fill, compact, publish, warm), then one open-loop
window per offered rate (harness.run_window) on the same table and
sessions.

    python3 benchmarks/chip/sweep.py --workload llcysa1.query --seed 5 \
        --rates 2,4,6,8,12 --seconds 20

Prints one JSON line per rate: queries due, answered, wrong, the cell's
query metrics, how late the generator ran, and how long the drain after
the window took (a backlog that grows through the window shows as a long
drain). The cell's rate is then fixed in its traffic file at about four
fifths of the knee.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench import entry, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import numpy as np

    try:
        op = entry.open_cell(args.workload)
    except entry.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 2
    cell = op.cell
    drain_s = float(cell.traffic["queries"]["drain_s"])
    prep = harness.prepare(cell, args.seed, args.seconds, op.mesh, log=lambda m: print(m, flush=True))
    base = prep.analysts
    print(json.dumps({"setup_s": time.perf_counter() - T_PROCESS,
                      "warm_wrong": prep.checks["warm_queries_wrong"]["value"]}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        prep.analysts = an = base.replan(rate, args.seed + i + 1, args.seconds)
        t0, t_end = harness.run_window(prep, args.seconds)
        an.drain(t_end + drain_s)
        art = harness.Artifacts(cell=cell.name, traced=False, window_s=t_end - t0, queries=an.plan)
        rec = {"rate": rate, "due": len(an.plan), "answered": sum(q.ok for q in an.plan),
               "drain_s": time.perf_counter() - t_end}
        rec.update({k: v["value"] for k, v in an.checks(prep.ref).items()})
        rec.update(an.notes())
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                rec[m["name"]] = harness.metric_reader(m["name"])(art)
        by_tier = {}
        for q in an.plan:
            by_tier.setdefault(q.tier, []).append(q.total_s())
        rec["total_s_median_by_tier"] = {k: float(np.median(v)) for k, v in sorted(by_tier.items())}
        print(json.dumps(rec), flush=True)
    base.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
