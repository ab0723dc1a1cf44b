#!/usr/bin/env python3
"""A traced window of a cell, read by program: the device seconds and runs
of each stable-named device program, the first chip's idle time by the
program span that overlaps it, and the cell's per-layer metrics.

    python3 benchmarks/chip/trace_programs.py --workload llcysa1.ingest --seed 5 \
        --seconds 51 [--dump trace.json.gz]

The cell's own set-up (harness.prepare) and window (harness.run_window),
traced as run.py --trace 1 traces it. Prints one JSON line: the per-layer
metrics, the ingest plane's device time per million rows acknowledged and
per major, `programs` (chipbench/programs.py), the busy time it covers, and
`idle_gaps_by_span`. --dump also writes the window's trace in the plain
form of chipbench/programs.py's load, gzipped, to read offline. Needs the
chips the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", help="write the window's trace here (.json.gz)")
    args = ap.parse_args(argv)

    from chipbench import entry, harness, programs
    from chipbench import trace as tracemod

    try:
        op = entry.open_cell(args.workload)
    except entry.NoChip as e:
        print(f"trace_programs.py: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.obs import trace as obs_trace

    cell = op.cell
    log = lambda m: print(m, flush=True)  # noqa: E731
    prep = harness.prepare(cell, args.seed, args.seconds, op.mesh, log=log)
    writers, plane = prep.writers, prep.sysm.plane
    blocked0 = float(plane.blocked_seconds)
    trace_dir = CHECKOUT / ".bench_work" / "trace_programs"
    harness._clear_dir(trace_dir)
    tracemod.start(str(trace_dir))
    obs_trace.enable()
    obs_trace.clear()
    with jax.profiler.TraceAnnotation(tracemod.WINDOW):
        t0, t_end = harness.run_window(prep, args.seconds)
    spans = [r for r in obs_trace.get_tracer().records if r["t0"] >= 0.0]
    obs_trace.disable()
    tracemod.stop()
    t = time.perf_counter()
    trace = programs.load(str(trace_dir))
    red = tracemod.reduce(trace)
    progs = programs.programs(trace)
    by_span = programs.idle_gaps_by_span(trace)
    log(f"phase trace read: {time.perf_counter() - t}s")
    if args.dump:
        with gzip.open(args.dump, "wt") as f:
            json.dump(trace, f)
    harness._clear_dir(trace_dir)

    art = harness.Artifacts(cell=cell.name, traced=True, setup_s=t0 - T_PROCESS,
                            window_s=t_end - t0, spans=spans, trace=red)
    if writers is not None:
        art.writers = writers.n
        art.acked_rows = int(sum(writers.acked)) - writers.warm_rows
        art.blocked_s = float(plane.blocked_seconds) - blocked0
    rec = {"workload": cell.name, "seed": args.seed, "window_s": art.window_s,
           "acked_rows": art.acked_rows, "failed": writers.failed if writers else 0}
    for m in cell.per_layer:
        rec[m["name"]] = harness.metric_reader(m["name"])(art)
    append, major = progs.get("plane_append"), progs.get("plane_major")
    if append and art.acked_rows:
        rec["append_device_s_per_Mrow"] = append["s"] / (art.acked_rows / 1e6)
    if major and major["runs"]:
        rec["major_device_s_mean"] = major["s"] / major["runs"]
    if red is not None:
        rec["busy_s"] = red["busy_s"]
        rec["program_s_over_busy_s"] = sum(p["s"] for p in progs.values()) / red["busy_s"]
        rec["breakdown"] = red["breakdown"]
    rec["programs"] = progs
    rec["idle_gaps_by_span"] = by_span
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
