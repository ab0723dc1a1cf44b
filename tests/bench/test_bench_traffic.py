"""The traffic generators reproduce their inputs from the seed, and give
every seed the same amount of work in another order."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.generator import WebProxyGenerator

BIG = 2**31 + 977  # seeds may exceed 32 signed bits


@pytest.mark.parametrize("seed", [0, BIG])
def test_events_reproduce_from_the_seed(seed):
    a = WebProxyGenerator(seed, n_domains=50).gen_codes(500, 0, 3599)
    b = WebProxyGenerator(seed, n_domains=50).gen_codes(500, 0, 3599)
    c = WebProxyGenerator(seed + 1, n_domains=50).gen_codes(500, 0, 3599)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    ts, codes = a
    assert np.all(np.diff(ts) >= 0) and ts.min() >= 0 and ts.max() <= 3599
    vocab = WebProxyGenerator(seed, n_domains=50).vocabulary()
    for j, f in enumerate(("src_ip", "dst_ip", "domain")):
        assert codes[:, j].max() < len(vocab[f])
    assert np.array_equal(codes[:, 2], codes[:, 10])  # the referer is the domain's


class _Sys:
    field_ids = {"domain": 2}


def _plan(cell, seed, seconds=10.0):
    data = harness.make_data(cell.config, seed)
    return harness.Analysts(_Sys(), data, cell.traffic["queries"], seed, seconds).plan


def test_query_plan_reproduces_from_the_seed(tiny_cell):
    cell = tiny_cell("llcysa1.query")
    a, b, c = _plan(cell, BIG), _plan(cell, BIG), _plan(cell, BIG + 1)
    key = lambda p: [(q.due, q.tier, q.scheme, q.code) for q in p]  # noqa: E731
    assert key(a) == key(b) != key(c)
    # Every seed gets the same gaps and the same tier x scheme mix, in another order.
    gaps = lambda p: np.sort(np.diff([0.0] + [q.due for q in p]))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(c))
    mix = lambda p: sorted((q.tier, q.scheme) for q in p)  # noqa: E731
    assert mix(a) == mix(c)
    n = cell.traffic["queries"]["rate_per_s"] * 10.0
    assert len(a) == round(n) and a[-1].due <= 10.0


def test_tier_bands_pick_domains_by_row_count(tiny_cell):
    cell = tiny_cell("llcysa1.query")
    data = harness.make_data(cell.config, 3)
    bands = harness.tier_domains(data.codes, cell.traffic["queries"]["tiers"], 2,
                                 cell.config["n_domains"])
    counts = np.bincount(data.codes[:, 2], minlength=cell.config["n_domains"])
    assert counts[bands["A"]].min() > counts[bands["B"]].max() > counts[bands["C"]].max()
    with pytest.raises(harness.BenchError):
        harness.tier_domains(data.codes, {"X": {"rows": [10**9, 10**9]}}, 2, cell.config["n_domains"])
