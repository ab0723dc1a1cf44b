"""The fill-bucketed major: a major sorts only the head of each base slab
that holds the live fill (the host picks it from its exact fill mirrors,
pow2-bucketed), and leaves the plane's state exactly as a whole-slab major
would: keys, payloads, live counts, overflow and run counters, for the
event, index (dedup) and aggregate (sum) families."""
import importlib.util
import pathlib
from types import SimpleNamespace

import jax
import jax.monitoring
import numpy as np
import pytest

from repro.core import EventStore, web_proxy_schema
from repro.core.dist_ingest import DistIngestPlane, TabletGroup, _PlanePrograms
from repro.launch.mesh import make_dev_mesh
from repro.obs import trace as obs_trace

CAPACITY = 1 << 11
MEM_ROWS = 64
MAX_RUNS = 2
TABLETS = 4
SKEW = [0.7, 0.1, 0.1, 0.1]  # tablet 0 far fuller than the rest


def _plane(**kw):
    store = EventStore(web_proxy_schema())
    return DistIngestPlane.for_store(
        store, make_dev_mesh(1, 1), capacity=CAPACITY, tablets_per_device=TABLETS,
        mem_rows=MEM_ROWS, max_runs=MAX_RUNS, **kw,
    )


def _batches(seed, n, batch=300):
    """Skewed batches whose rev_ts repeat (index postings to dedup) and whose
    few codes share aggregate keys (counts to sum)."""
    rng = np.random.default_rng(seed)
    rts = rng.integers(0, 3000, n).astype(np.int32)
    cols = rng.integers(0, 6, (n, 12)).astype(np.int32)
    tab = rng.choice(TABLETS, size=n, p=SKEW).astype(np.int32)
    for off in range(0, n, batch):
        sl = slice(off, off + batch)
        yield rts[sl], cols[sl], tab[sl]


def _state(plane):
    return {k: np.asarray(jax.device_get(v)) for k, v in plane.groups[0].state.items()}


def _assert_same_state(a, b):
    sa, sb = _state(a), _state(b)
    assert sa.keys() == sb.keys()
    for k in sa:
        assert sa[k].dtype == sb[k].dtype, k
        np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)


@pytest.mark.parametrize("rows,overflows", [(2600, False), (4200, True)])
def test_bucketed_major_state_equals_whole_slab_major(monkeypatch, rows, overflows):
    bucketed, whole = _plane(), _plane()
    pr = bucketed.programs
    monkeypatch.setattr(whole.programs, "_major_bucket", lambda live: whole.programs.capacity)
    n_idx = len(pr.indexed_fids)
    seen = []  # (bucket, most ev / ix entries any tablet held before the major)
    run_major = TabletGroup._run_major

    def spy(group):
        before = {k: np.asarray(jax.device_get(group.state[k])) for k in (
            "ev_base_n", "ev_run_n", "ix_base_n", "ix_run_n", "ag_base_n", "ag_run_n")}
        sort_rows = run_major(group)
        if group is bucketed.groups[0]:
            live = {p: before[f"{p}_base_n"] + before[f"{p}_run_n"].sum(1) for p in ("ev", "ix", "ag")}
            seen.append((sort_rows, int(live["ev"].max()), int(live["ix"].max()), int(live["ag"].max())))
        return sort_rows

    monkeypatch.setattr(TabletGroup, "_run_major", spy)
    for rts, cols, tab in _batches(7, rows):
        bucketed.ingest(rts, cols, tab)
        whole.ingest(rts, cols, tab)
        _assert_same_state(bucketed, whole)
    bucketed.compact()
    whole.compact()
    _assert_same_state(bucketed, whole)

    buckets = [b for b, *_ in seen]
    assert set(buckets) == set(pr.major_buckets())  # every edge crossed, the top reached
    for b, ev, ix, ag in seen:
        if b < pr.capacity:  # below the top the head holds every live entry
            assert ev <= b and ix <= n_idx * b and ag <= n_idx * b
    tel = bucketed.telemetry()
    assert (int(tel["overflow"].sum()) > 0) == overflows
    assert int(tel["ix_base_n"].sum()) < n_idx * rows  # repeated postings collapsed
    assert int(tel["ag_base_n"].sum()) < n_idx * rows  # repeated keys summed


@pytest.mark.parametrize("capacity,mem_rows,max_runs", [
    (1 << 11, 64, 2), (1 << 18, 4096, 4), (1 << 20, 4096, 4), (10_000, 512, 2),
    (3000, 64, 3), (256, 128, 4),
])
def test_major_bucket_is_never_below_the_live_rows(capacity, mem_rows, max_runs):
    pr = _PlanePrograms(
        make_dev_mesh(1, 1), 12, capacity, TABLETS, mem_rows, max_runs,
        append_rows=1024, indexed_fids=tuple(range(12)), agg_bucket_s=3600,
        kernel_backend="auto",
    )
    buckets = pr.major_buckets()
    assert list(buckets) == sorted(set(buckets)) and buckets[-1] == capacity
    rng = np.random.default_rng(capacity)
    edges = [x + d for b in buckets for x in (b,) for d in (-1, 0, 1)]
    for live in [0, 1, *edges, *rng.integers(0, 2 * capacity, 200).tolist()]:
        b = pr._major_bucket(int(live))
        assert b in buckets
        assert b >= live or b == capacity
        smaller = [x for x in buckets if x < b]
        assert not smaller or smaller[-1] < live  # the smallest bucket that holds it


def test_warm_majors_add_no_program_and_compile_nothing():
    plane = _plane()
    pr = plane.programs
    plane.precompile()
    plane.warm_compaction()
    rng = np.random.default_rng(3)
    # One small append warms the append program; it trips no flush.
    plane.ingest(rng.integers(0, 3000, 8).astype(np.int32),
                 rng.integers(0, 6, (8, 12)).astype(np.int32), np.zeros(8, np.int32))
    steps = set(pr._steps)
    compiles = []

    def note(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(note)
    obs_trace.enable()
    obs_trace.clear()
    try:
        for rts, cols, tab in _batches(11, 2600):
            plane.ingest(rts, cols, tab)
        majors = [r for r in obs_trace.get_tracer().records if r["name"] == "ingest.major"]
    finally:
        obs_trace.disable()
        jax.monitoring.unregister_event_duration_listener(note)
    assert compiles == []
    assert set(pr._steps) == steps
    sorted_rows = [r["args"]["sort_rows"] for r in majors]
    assert len(set(sorted_rows)) >= 3  # majors at rising fills
    assert sorted_rows == sorted(sorted_rows)
    assert all(r["args"]["capacity_rows"] == CAPACITY for r in majors)
    assert set(sorted_rows) <= set(pr.major_buckets())


def _reader():
    path = pathlib.Path(__file__).parents[1] / "benchmarks/chip/metrics/major_sorted_share.py"
    spec = importlib.util.spec_from_file_location("major_sorted_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(name, t0, dur, **args):
    return {"name": name, "cat": "ingest", "t0": t0, "dur": dur, "args": args}


def test_major_sorted_share_reads_the_window_majors():
    read = _reader()
    art = SimpleNamespace(window_s=10.0, spans=[
        _span("ingest.major", 1.0, 0.5, sort_rows=16384, capacity_rows=1 << 18),
        _span("ingest.major", 5.0, 0.5, sort_rows=1 << 17, capacity_rows=1 << 18),
        _span("ingest.major", 9.8, 0.5, sort_rows=1 << 18, capacity_rows=1 << 18),  # ends after
        _span("ingest.append", 2.0, 0.1, rows=1024),
    ])
    assert read(art) == pytest.approx((16384 + (1 << 17)) / (2 << 18))
    # A program whose majors carry no bucket (before the bucketed major) reads nothing.
    art.spans = [_span("ingest.major", 1.0, 0.5, group=0)]
    assert read(art) is None
