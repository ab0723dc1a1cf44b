"""Device time by stable program name and idle time by program span, checked
by hand on a small trace in the form `programs.load` gives and on a trace
recorded on the chip; the metric readers of the program's own spans; and a
traced run that reports them.

The chip trace (fixtures/llcysa1_ingest_major.json.gz) is a window of
`llcysa1.ingest` on one TPU v5e, taken with `trace_programs.py --dump`: the
1.03 s from 0.25 s before the window's second `jit_plane_major` run to
0.25 s after it, every event overlapping that span kept whole, and the
`bench.window` annotation cut to it.
"""
import gzip
import json
import math
import time
from pathlib import Path

import pytest

from chipbench import harness, programs
from chipbench import trace as T
from repro.launch.mesh import make_dev_mesh


def _small():
    return {
        "devices": {
            "/device:TPU:0": [[10, 20, "a"], [15, 30, "b"], [50, 60, "a"], [0, 8, "a"]],
            "/device:TPU:1": [[0, 100, "c"]],
        },
        "modules": {
            "/device:TPU:0": [[0, 8, "jit_plane_append(1)"], [10, 30, "jit_plane_major(2)"],
                              [50, 60, "jit_plane_append(1)"]],
            "/device:TPU:1": [[0, 100, "jit_plane_major(2)"]],
        },
        "host": [
            [5, 95, T.WINDOW, "main"],
            [30, 50, "bench.add", "writer-0"],
            [29, 49, "ingest.append", "writer-0"],
            [30, 49, "ingest.major", "writer-0"],  # nested in the append
            [60, 95, "PjitFunction(x)", "dispatcher"],
            [62, 70, "ingest.encode", "writer-1"],
        ],
    }


def test_programs_by_hand():
    p = programs.programs(_small())
    # append: device 0 runs [5,8] + [50,60] of the window, device 1 none
    assert p["plane_append"]["s"] == pytest.approx(13 / 2 * 1e-9)
    assert p["plane_append"]["runs"] == pytest.approx(1.0)
    # major: device 0 [10,30], device 1 the whole window
    assert p["plane_major"]["s"] == pytest.approx((20 + 90) / 2 * 1e-9)
    assert p["plane_major"]["runs"] == pytest.approx(1.0)
    # here every device op runs inside a module: programs cover busy time
    busy = T.reduce(_small())["busy_s"]
    assert sum(v["s"] for v in p.values()) == pytest.approx(busy)


@pytest.mark.parametrize("module,name", [
    ("jit_plane_append(1234)", "plane_append"), ("jit_query_density", "query_density"),
    ("jit_plane_seal.1", "plane_seal"), ("copy_start", "copy_start"),
])
def test_program_name(module, name):
    assert programs.program_name(module) == name


def test_idle_gaps_by_span_by_hand():
    gaps = dict(programs.idle_gaps_by_span(_small()))
    # device 0's gaps: [8,10] nothing on the host; [30,50] the append and the
    # major nested in it overlap it alike and the innermost, the major, wins
    # over the benchmark's longer annotation; [60,95] a program span beats a
    # longer host event
    assert gaps == pytest.approx({"host idle": 2e-9, "ingest.major": 20e-9,
                                  "ingest.encode": 35e-9})


def test_idle_gaps_without_program_spans_match_trace_reduce():
    t = _small()
    t["host"] = [h for h in t["host"] if not h[2].startswith(programs.SPAN_PREFIXES)]
    assert dict(programs.idle_gaps_by_span(t)) == pytest.approx(
        dict(T.reduce(t)["breakdown"]["idle_gaps"]))


def test_no_window_or_no_modules_gives_nothing():
    t = _small()
    assert programs.programs({**t, "host": t["host"][1:]}) == {}
    assert programs.idle_gaps_by_span({**t, "host": t["host"][1:]}) == []
    assert programs.programs({k: v for k, v in t.items() if k != "modules"}) == {}


def _art(spans, window_s=10.0, writers=4, acked_rows=2_000_000):
    return harness.Artifacts(cell="c", traced=True, window_s=window_s, writers=writers,
                             acked_rows=acked_rows, spans=spans)


def _rec(name, t0, dur, cat="ingest", **args):
    return {"name": name, "cat": cat, "t0": t0, "dur": dur, "args": args}


def test_encode_reader_sums_window_spans_per_million_rows():
    read = harness.metric_reader("encode_s_per_Mrow")
    spans = [_rec("ingest.encode", 1.0, 0.5, rows=4096), _rec("ingest.encode", 2.0, 1.5, rows=4096),
             _rec("ingest.encode", 9.5, 1.0, rows=4096),  # ends after the window
             _rec("ingest.append", 3.0, 4.0, rows=4096)]
    assert read(_art(spans)) == pytest.approx(2.0 / 2.0)
    assert read(_art(spans[3:])) is None  # a program without the span reads nothing


def test_lock_wait_reader_sums_append_waits_over_writers_and_window():
    read = harness.metric_reader("writer_lock_wait_share")
    spans = [_rec("lock/plane_lock", 1.0, 0.1, cat="lock", owner="ingest_append", wait_s=3.0),
             _rec("lock/plane_lock_g1", 2.0, 0.1, cat="lock", owner="ingest_append", wait_s=5.0),
             _rec("lock/plane_lock", 3.0, 0.1, cat="lock", owner="publish_seal", wait_s=7.0),
             _rec("lock/plane_step_lock", 4.0, 0.1, cat="lock", owner="step_build", wait_s=7.0)]
    assert read(_art(spans)) == pytest.approx(8.0 / (4 * 10.0))
    old = [dict(s, args={"owner": s["args"]["owner"]}) for s in spans]  # no wait_s on the span
    assert read(_art(old)) is None
    assert read(_art(spans, writers=0)) is None


def test_traced_ingest_run_reports_the_span_metrics(tiny_cell, tmp_path):
    out = harness.execute(tiny_cell("llcysa1.ingest"), 2**31 + 11, 2.0, True, make_dev_mesh(1, 1),
                          time.perf_counter(), log=lambda m: None, work_dir=tmp_path)
    assert out.correct
    for name in ("encode_s_per_Mrow", "writer_lock_wait_share"):
        v = out.metrics[name]["value"]
        assert math.isfinite(v) and v >= 0
    assert out.metrics["writer_lock_wait_share"]["value"] < 1.0


FIXTURE = Path(__file__).parent / "fixtures" / "llcysa1_ingest_major.json.gz"


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_chip_trace_window_is_found(chip_trace):
    lo, hi = T.window_of(chip_trace)
    assert 0.9 < (hi - lo) / 1e9 < 1.1
    assert T.reduce(chip_trace)["n_devices"] == 1


def test_chip_trace_programs_cover_busy_time(chip_trace):
    p = programs.programs(chip_trace)
    assert {"plane_append", "plane_major"} <= set(p)
    assert p["plane_major"]["runs"] == 1
    busy = T.reduce(chip_trace)["busy_s"]
    assert sum(v["s"] for v in p.values()) == pytest.approx(busy, rel=0.05)


def test_chip_trace_major_span_encloses_the_major_run(chip_trace):
    """The program's ingest.major annotation (host) and the device's
    jit_plane_major run share one clock: the span, which ends in
    block_until_ready, holds the run."""
    spans = [(s, e) for s, e, name, _ in chip_trace["host"] if name == "ingest.major"]
    (run,) = [(s, e) for evs in chip_trace["modules"].values() for s, e, name in evs
              if programs.program_name(name) == "plane_major"]
    assert any(s <= run[0] and run[1] <= e for s, e in spans)


def test_chip_trace_idle_gaps_by_span_file_all_idle_time(chip_trace):
    r = T.reduce(chip_trace)
    by = dict(programs.idle_gaps_by_span(chip_trace, top=10**6))
    assert sum(by.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert any(k.startswith("ingest.") for k in by)
