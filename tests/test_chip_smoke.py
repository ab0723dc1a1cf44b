"""chip_smoke.py rehearsed on the CPU at a tiny size: the same entry points
(DistBatchWriter threads -> DistIngestPlane -> QueryService sessions)
and the same numpy reference checks as the chip run, plus the two ways
the script must refuse: no TPU, and a result that disagrees with the
reference."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from repro.launch.mesh import make_dev_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = chip_smoke  # dataclasses resolve their module
_spec.loader.exec_module(chip_smoke)

TINY = chip_smoke.SmokeConfig(
    tablets_per_device=2, capacity=1 << 12, n_events=6000, mem_rows=128, max_runs=2
)


def test_tiny_run_agrees_with_reference():
    rep = chip_smoke.run(make_dev_mesh(1, 1), TINY, seed=3, log=lambda m: None)
    assert rep["overflow"] == 0
    assert rep["major"] >= 1 and rep["minor"] >= rep["major"]
    assert len(rep["queries"]) == 12  # A/B/C x four schemes, each checked
    counts = {}
    for name, q in rep["queries"].items():
        tier, _ = name.split("/")
        counts.setdefault(tier, set()).add(q["count"])
        assert q["ttfr_s"] is not None and q["ttfr_s"] > 0
    # Every scheme returns the same count per tier, and the tiers are
    # ordered by selectivity.
    assert all(len(c) == 1 for c in counts.values())
    (a,), (b,), (c,) = counts["A"], counts["B"], counts["C"]
    assert a > b > c
    assert rep["aggregate_rows"] > 0
    assert sum(rep["rows_per_chip"].values()) == TINY.n_events


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def _reference(n=400, n_tablets=2, seed=0):
    from repro.core import web_proxy_schema

    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, 1000, n)).astype(np.int64)
    codes = rng.integers(0, 3, (n, 12)).astype(np.int32)
    tablet = rng.integers(0, n_tablets, n)
    return chip_smoke.Reference(ts, codes, tablet, n_tablets, web_proxy_schema())


class _Batch:
    def __init__(self, lo, hi, count, ts, cols):
        self.lo, self.hi, self.count, self.ts, self.cols = lo, hi, count, ts, cols


def _true_batch(ref, rows, lo, hi):
    """What the device must deliver: count, and each tablet's newest rows."""
    sel = rows[(ref.ts[rows] >= lo) & (ref.ts[rows] <= hi)]
    keep = np.concatenate(
        [sel[ref.tablet[sel] == t][-chip_smoke.TOP_K:] for t in range(ref.n_tablets)]
    )
    return _Batch(lo, hi, sel.size, ref.ts[keep], ref.codes[keep])


def test_reference_accepts_the_true_answer_and_rejects_wrong_ones():
    ref = _reference()
    rows = ref.matching("domain", 1)
    good = _true_batch(ref, rows, 100, 900)
    ref.check_batch(rows, good)
    with pytest.raises(chip_smoke.SmokeFailure):  # count off by one
        ref.check_batch(rows, _Batch(100, 900, good.count + 1, good.ts, good.cols))
    with pytest.raises(chip_smoke.SmokeFailure):  # a row dropped
        ref.check_batch(rows, _Batch(100, 900, good.count, good.ts[1:], good.cols[1:]))
    wrong = good.cols.copy()
    wrong[0, 0] += 1  # a delivered row that matches nothing
    with pytest.raises(chip_smoke.SmokeFailure):
        ref.check_batch(rows, _Batch(100, 900, good.count, good.ts, wrong))
    with pytest.raises(chip_smoke.SmokeFailure):  # batches that do not tile the range
        ref.check_stream(rows, [good], 0, 999)
