"""Whole runs of each one-chip cell at a CPU size, driven past the
harness's look for a chip: sound, they come out correct; with a fault
planted under the timed path, `correct` comes out false."""
import time

import pytest

from chipbench import faults, harness
from repro.launch.mesh import make_dev_mesh


def _run(cell, fault, tmp_path, traced=False, seed=2**31 + 7):
    with faults.plant(fault):
        return harness.execute(cell, seed, 2.0, traced, make_dev_mesh(1, 1), time.perf_counter(),
                               log=lambda m: None, work_dir=tmp_path)


@pytest.mark.parametrize("traced", [False, True])
def test_ingest_cell_sound_run_is_correct(tiny_cell, tmp_path, traced):
    out = _run(tiny_cell("llcysa1.ingest"), "none", tmp_path, traced)
    assert out.correct and out.failed == 0 and out.attempted > 0
    assert all(c["value"] == 0 for c in out.checks.values())
    assert "readback_queries_wrong" in out.checks and "tablet_rows_off" in out.checks
    want = {"writer_blocked_share", "major_s_mean"} if traced else {"ingest_rows_per_s", "setup_s"}
    assert want <= set(out.metrics)  # the device trace's metric needs a device plane
    assert out.notes["compiles_in_window"] == {"lowered": 0, "compiled": 0}


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_answer"])
def test_ingest_cell_fault_is_not_correct(tiny_cell, tmp_path, fault):
    out = _run(tiny_cell("llcysa1.ingest"), fault, tmp_path)
    assert not out.correct
