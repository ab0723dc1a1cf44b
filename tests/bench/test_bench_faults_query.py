"""The query cell (held out of BENCHMARK.json until its rate is fixed on
the chip, held_out.json) at a CPU size: sound, every query due in the window is
answered and exact; with a fault planted, `correct` comes out false."""
import time

import pytest

from chipbench import faults, harness
from repro.launch.mesh import make_dev_mesh


def _run(cell, fault, tmp_path, seed=2**31 + 11):
    with faults.plant(fault):
        return harness.execute(cell, seed, 2.0, False, make_dev_mesh(1, 1), time.perf_counter(),
                               log=lambda m: None, work_dir=tmp_path)


def test_query_cell_sound_run_is_correct(tiny_cell, tmp_path):
    cell = tiny_cell("llcysa1.query")
    out = _run(cell, "none", tmp_path)
    assert out.correct and out.failed == 0
    assert out.attempted == round(cell.traffic["queries"]["rate_per_s"] * 2.0)
    assert all(c["value"] == 0 for c in out.checks.values())
    assert {"ttfr_p50_s", "ttfr_p95_s", "query_total_p95_s", "setup_s"} <= set(out.metrics)
    assert out.metrics["ttfr_p50_s"]["value"] <= out.metrics["ttfr_p95_s"]["value"]
    assert out.notes["compiles_in_window"] == {"lowered": 0, "compiled": 0}


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_answer"])
def test_query_cell_fault_is_not_correct(tiny_cell, tmp_path, fault):
    assert not _run(tiny_cell("llcysa1.query"), fault, tmp_path).correct
