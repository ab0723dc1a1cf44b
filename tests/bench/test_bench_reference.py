"""The copied numpy reference accepts the true answer and flags each way
a delivered answer can be wrong; its placement replay is the writers'."""
import numpy as np
import pytest

from chipbench import reference as refmod

TOP_K = 4


def _reference(n=400, n_tablets=2, seed=0):
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, 1000, n)).astype(np.int64)
    codes = rng.integers(0, 3, (n, 12)).astype(np.int32)
    tablet = rng.integers(0, n_tablets, n)
    return refmod.Reference(ts, codes, tablet, n_tablets, TOP_K, {"domain": 2, "status": 5})


class _Batch:
    def __init__(self, lo, hi, count, ts, cols):
        self.lo, self.hi, self.count, self.ts, self.cols = lo, hi, count, ts, cols


def _true_batch(ref, rows, lo, hi):
    """What the device must deliver: count, and each tablet's newest rows."""
    sel = rows[(ref.ts[rows] >= lo) & (ref.ts[rows] <= hi)]
    keep = np.concatenate([sel[ref.tablet[sel] == t][-TOP_K:] for t in range(ref.n_tablets)])
    return _Batch(lo, hi, sel.size, ref.ts[keep], ref.codes[keep])


def test_true_answer_passes():
    ref = _reference()
    rows = ref.matching("domain", 1)
    parts = [_true_batch(ref, rows, 0, 499), _true_batch(ref, rows, 500, 999)]
    assert ref.check_batch(rows, parts[0]) == []
    assert ref.check_stream(rows, parts, 0, 999) == (0, [])


@pytest.mark.parametrize("fault", ["count", "dropped", "foreign", "tiling"])
def test_perturbed_answer_is_flagged(fault):
    ref = _reference()
    rows = ref.matching("domain", 1)
    good = _true_batch(ref, rows, 100, 900)
    if fault == "count":
        bad = _Batch(100, 900, good.count + 1, good.ts, good.cols)
    elif fault == "dropped":
        bad = _Batch(100, 900, good.count, good.ts[1:], good.cols[1:])
    elif fault == "foreign":
        cols = good.cols.copy()
        cols[0, 0] += 1  # a delivered row that matches nothing
        bad = _Batch(100, 900, good.count, good.ts, cols)
    else:  # the batches do not cover the query's range
        wrong, faults = ref.check_stream(rows, [good], 0, 999)
        assert wrong == 0 and faults
        return
    assert ref.check_batch(rows, bad)
    assert ref.check_stream(rows, [bad], 100, 900)[0] == 1


def test_aggregate_faults_count_every_differing_cell():
    ref = _reference()
    want = ref.count_per("status", 100, 0, 999)
    assert sum(want.values()) == len(ref.ts)
    assert refmod.aggregate_faults(want, dict(want)) == 0
    got = dict(want)
    k = next(iter(got))
    got[k] += 1
    got[(99, 0)] = 1
    del got[sorted(want)[-1]]
    assert refmod.aggregate_faults(want, got) == 3


def test_placement_replay_matches_the_writer():
    """A DistBatchWriter's tablets, replayed from the rows it wrote."""
    from repro.core import keypack

    rng = np.random.default_rng(5)
    ts = np.sort(rng.integers(0, 14400, 300)).astype(np.int64)
    codes = rng.integers(0, 1000, (300, 12)).astype(np.int32)
    nonce = np.arange(300, dtype=np.int64)
    h = keypack.short_hash(*(codes[:, j] for j in range(12)), ts, nonce, np.int64(3))
    assert np.array_equal(refmod.writer_tablets(ts, codes, nonce, np.int64(3), 8), h % 8)
    assert refmod.TS_MAX == keypack.TS_MAX
