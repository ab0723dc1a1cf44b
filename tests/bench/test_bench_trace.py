"""The reduction from a device trace to busy time, idle share and the
breakdown, checked by hand on a small trace in the form `trace.load`
gives."""
import pytest

from chipbench import trace as T


def _small():
    return {
        "devices": {
            "/device:TPU:0": [[10, 20, "a"], [15, 30, "b"], [50, 60, "a"], [0, 8, "a"]],
            "/device:TPU:1": [[0, 100, "c"]],
        },
        "host": [
            [5, 95, T.WINDOW, "main"],
            [30, 50, "bench.add", "writer-0"],
            [28, 52, "PjitFunction(step)", "writer-0"],
            [60, 95, "PjitFunction(x)", "dispatcher"],
        ],
    }


def test_small_trace_by_hand():
    r = T.reduce(_small())
    assert r["window_s"] == pytest.approx(90e-9)
    # device 0: [5,8] + [10,30] + [50,60] = 33 ns; device 1: the whole window
    assert r["busy_s"] == pytest.approx((33 + 90) / 2 * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - (33 + 90) / 2 / 90)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["c"] == pytest.approx(45e-9) and ops["a"] == pytest.approx((3 + 10 + 10) / 2 * 1e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # gaps of device 0: [8,10] nothing on the host; [30,50] the benchmark's
    # own annotation wins over a longer host event; [60,95] the host event
    assert gaps == pytest.approx({"host idle": 2e-9, "bench.add": 20e-9,
                                  "dispatcher:PjitFunction(x)": 35e-9})


def test_no_window_or_no_device_gives_nothing():
    t = _small()
    assert T.reduce({"devices": t["devices"], "host": t["host"][1:]}) is None
    assert T.reduce({"devices": {}, "host": t["host"]}) is None


def test_gaps_past_the_longest_are_summed_together():
    n = T.ATTRIBUTED_GAPS + 50
    # ops of 1 ns, each followed by a gap of 1 + i ns; the window covers them all
    starts = [i * (i + 3) // 2 for i in range(n)]
    evs = [[s, s + 1, "op"] for s in starts]
    hi = starts[-1] + 1
    t = {"devices": {"/device:TPU:0": evs}, "host": [[0, hi, T.WINDOW, "main"]]}
    r = T.reduce(t)
    gaps = dict(r["breakdown"]["idle_gaps"])
    short = sorted(s2 - (s1 + 1) for s1, s2 in zip(starts, starts[1:]))[:n - 1 - T.ATTRIBUTED_GAPS]
    assert gaps["shorter gaps"] == pytest.approx(sum(short) * 1e-9)
    assert sum(gaps.values()) == pytest.approx((hi - n) * 1e-9)
    assert r["busy_s"] == pytest.approx(n * 1e-9)
