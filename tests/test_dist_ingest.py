"""Distributed ingest plane: merge kernel oracles, host-vs-device
exact-agreement, live incremental visibility, and backpressure telemetry."""
import numpy as np
import pytest
from hypothesis import example, given, settings
from hypothesis import strategies as st

from repro.core import AggregateSpec, And, Eq, EventStore, Not, Or, web_proxy_schema
from repro.core.dist_ingest import (
    DistBatchWriter,
    DistIngestPlane,
    check_tablet_guidance,
)
from repro.core.dist_query import DistQueryProcessor, from_event_store
from repro.core.ingest import IngestMetrics
from repro.core.query import QueryProcessor
from repro.kernels.common import split_key_lanes
from repro.kernels.merge_runs import (
    merge_base_runs_device,
    merge_ranks_pallas,
    merge_ranks_ref,
    merge_sorted_device,
    merge_sorted_runs,
)
from repro.launch.mesh import make_dev_mesh

import jax
import jax.numpy as jnp

INT32_MAX = np.iinfo(np.int32).max


# ------------------------------------------------------- merge_runs kernel
def _random_runs(rng, k, max_n, key_bits=53, dup_frac=0.3):
    """Sorted int64 runs with forced intra- and inter-run duplicates."""
    runs = []
    shared = rng.integers(0, 1 << key_bits, size=max(max_n // 4, 1))
    for _ in range(k):
        n = int(rng.integers(0, max_n + 1))
        fresh = rng.integers(0, 1 << key_bits, size=n)
        take_shared = rng.random(n) < dup_frac
        keys = np.where(take_shared, rng.choice(shared, size=n) if n else fresh, fresh)
        keys = np.sort(keys.astype(np.int64))
        cols = rng.integers(0, 1000, size=(n, 3)).astype(np.int32)
        runs.append((keys, cols))
    return runs


@given(k=st.integers(2, 6), max_n=st.integers(1, 800), seed=st.integers(0, 2**31))
@settings(max_examples=20, deadline=None)
@example(k=2, max_n=1, seed=2).via("discovered failure: every run empty")
def test_merge_sorted_runs_vs_numpy(k, max_n, seed):
    """Host merge == concat + stable argsort (the placeholder it retires),
    including duplicate keys and empty runs."""
    rng = np.random.default_rng(seed)
    runs = _random_runs(rng, k, max_n)
    mk, mc = merge_sorted_runs(runs)
    all_k = np.concatenate([kk for kk, _ in runs]) if runs else np.empty(0, np.int64)
    all_c = np.concatenate([cc for _, cc in runs]) if runs else np.empty((0, 3), np.int32)
    order = np.argsort(all_k, kind="stable")
    np.testing.assert_array_equal(mk, all_k[order])
    np.testing.assert_array_equal(mc, all_c[order])


@given(k=st.integers(2, 5), r_log=st.integers(1, 9), seed=st.integers(0, 2**31))
@settings(max_examples=20, deadline=None)
def test_merge_ranks_pallas_vs_ref(k, r_log, seed):
    """Pallas rank kernel == jnp searchsorted reference on sentinel-padded
    lanes — ranks must be a permutation even with heavy key duplication."""
    rng = np.random.default_rng(seed)
    r = 1 << r_log
    keys = np.full((k, r), np.iinfo(np.int64).max, np.int64)
    for i in range(k):
        n = int(rng.integers(0, r + 1))
        keys[i, :n] = np.sort(rng.integers(0, 50, size=n).astype(np.int64))  # dense dups
    hi, lo = split_key_lanes(keys.reshape(-1))
    hi, lo = hi.reshape(k, r), lo.reshape(k, r)
    got = np.asarray(merge_ranks_pallas(jnp.asarray(hi), jnp.asarray(lo), interpret=True, block=min(64, r)))
    want = np.asarray(merge_ranks_ref(hi, lo))
    np.testing.assert_array_equal(got, want)
    assert sorted(got.reshape(-1).tolist()) == list(range(k * r))


def test_merge_sorted_device_pad_sentinels():
    """Device merge: sentinel padding stays a contiguous tail and payload
    columns travel with their keys."""
    rng = np.random.default_rng(5)
    k, r, f = 3, 64, 2
    keys = np.full((k, r), INT32_MAX, np.int32)
    cols = np.zeros((k, r, f), np.int32)
    ns = [40, 0, 64]  # one empty run, one exactly full
    for i, n in enumerate(ns):
        keys[i, :n] = np.sort(rng.integers(0, 20, size=n).astype(np.int32))
        cols[i, :n] = rng.integers(1, 100, size=(n, f))
    mk, mc = merge_sorted_device(jnp.asarray(keys), jnp.asarray(cols))
    mk, mc = np.asarray(mk), np.asarray(mc)
    n_tot = sum(ns)
    real_k = np.concatenate([keys[i, : ns[i]] for i in range(k)])
    real_c = np.concatenate([cols[i, : ns[i]] for i in range(k)])
    order = np.argsort(real_k, kind="stable")
    np.testing.assert_array_equal(mk[:n_tot], real_k[order])
    np.testing.assert_array_equal(mc[:n_tot], real_c[order])
    assert (mk[n_tot:] == INT32_MAX).all()


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_merge_base_runs_device_backends_agree(dtype):
    """Major-compaction merge: the one-sort "auto" path and the pallas
    two-stage path give the same arrays, equal to a stable sort of base
    then runs (base wins ties, then earlier runs), sentinels last."""
    rng = np.random.default_rng(11)
    sent = np.iinfo(dtype).max
    c, k, r, w = 96, 3, 32, 2
    base_k = np.full(c, sent, dtype)
    base_c = np.zeros((c, w), np.int32)
    base_k[:70] = np.sort(rng.integers(0, 30, 70))
    base_c[:70] = rng.integers(1, 100, (70, w))
    run_k = np.full((k, r), sent, dtype)
    run_c = np.zeros((k, r, w), np.int32)
    ns = [20, 0, 32]
    for i, n in enumerate(ns):
        run_k[i, :n] = np.sort(rng.integers(0, 30, n))
        run_c[i, :n] = rng.integers(100, 200, (n, w))
    got = {
        b: tuple(np.asarray(a) for a in merge_base_runs_device(
            jnp.asarray(base_k), jnp.asarray(base_c),
            jnp.asarray(run_k), jnp.asarray(run_c), backend=b))
        for b in ("ref", "pallas")
    }
    np.testing.assert_array_equal(got["ref"][0], got["pallas"][0])
    np.testing.assert_array_equal(got["ref"][1], got["pallas"][1])
    real_k = np.concatenate([base_k[:70]] + [run_k[i, :n] for i, n in enumerate(ns)])
    real_c = np.concatenate([base_c[:70]] + [run_c[i, :n] for i, n in enumerate(ns)])
    order = np.argsort(real_k, kind="stable")
    n_tot = real_k.size
    mk, mc = got["ref"]
    np.testing.assert_array_equal(mk[:n_tot], real_k[order])
    np.testing.assert_array_equal(mc[:n_tot], real_c[order])
    assert (mk[n_tot:] == sent).all() and mk.size == c + k * r


def test_tablet_major_compaction_uses_merge(monkeypatch):
    """Host Tablet major compaction goes through the merge kernel path and
    preserves scan results."""
    from repro.core.tables import Tablet

    t = Tablet(0, width=2, flush_rows=64, max_runs=2)
    rng = np.random.default_rng(9)
    all_k, all_c = [], []
    for _ in range(6):
        keys = np.sort(rng.integers(0, 10_000, size=64).astype(np.int64))
        cols = rng.integers(0, 50, size=(64, 2)).astype(np.int32)
        t.insert(keys, cols)
        all_k.append(keys)
        all_c.append(cols)
    t.compact()
    assert len(t.runs) == 1 and t.major_compactions >= 1
    got_k, got_c = t.scan_range(0, 10_001)
    flat_k = np.concatenate(all_k)
    flat_c = np.concatenate(all_c)
    order = np.argsort(flat_k, kind="stable")
    np.testing.assert_array_equal(got_k, flat_k[order])
    # Duplicate keys may reorder their payload between insertion batches;
    # compare as multisets per key.
    assert sorted(map(tuple, got_c)) == sorted(map(tuple, flat_c[order]))


# ------------------------------------------------- host-vs-device agreement
N_EVENTS = 12_000
T_SPAN = 4 * 3600


def _gen(seed=3, n=N_EVENTS):
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, T_SPAN, n))
    vals = {
        "domain": rng.choice(["a.com", "b.com", "c.com"], p=[0.6, 0.3, 0.1], size=n).tolist(),
        "method": rng.choice(["GET", "POST"], size=n).tolist(),
        "status": rng.choice(["200", "404"], size=n).tolist(),
        "bytes_out": rng.integers(10, 5000, size=n).astype(str).tolist(),
    }
    return ts, vals


@pytest.fixture(scope="module")
def ingested():
    """The same events through BOTH planes: host EventStore ingest and
    DistBatchWriter -> device tablets (2 tablets on the 1-device mesh)."""
    ts, vals = _gen()
    store = EventStore(web_proxy_schema(), n_shards=4)
    store.ingest(ts, vals)
    store.flush_all()
    store.compact_all()
    mesh = make_dev_mesh(1, 1)
    plane = DistIngestPlane(
        mesh, store.schema.n_fields, capacity=N_EVENTS + 1024,
        tablets_per_device=2, mem_rows=2048, max_runs=3, append_rows=512,
    )
    w = DistBatchWriter(store, plane, batch_rows=1500)
    step = 997  # deliberately misaligned with every internal batch size
    for off in range(0, len(ts), step):
        sl = slice(off, off + step)
        w.add(ts[sl], {k: v[sl] for k, v in vals.items()})
    w.close()
    dq = DistQueryProcessor(store, plane=plane)
    return store, plane, dq, ts, {k: np.array(v) for k, v in vals.items()}


TREES = [
    (Eq("domain", "c.com"), lambda v: v["domain"] == "c.com"),
    (
        And(Eq("domain", "b.com"), Not(Eq("method", "POST"))),
        lambda v: (v["domain"] == "b.com") & (v["method"] != "POST"),
    ),
    (
        Or(Eq("status", "404"), Eq("domain", "c.com")),
        lambda v: (v["status"] == "404") | (v["domain"] == "c.com"),
    ),
]


@pytest.mark.parametrize("tree,mask_fn", TREES)
@pytest.mark.parametrize("t_range", [(0, T_SPAN), (1800, 5400)])
def test_device_ingest_count_matches_host(ingested, tree, mask_fn, t_range):
    _, _, dq, ts, vals = ingested
    t0, t1 = t_range
    count, top_ts, _ = dq.scan_range(tree, t0, t1)
    expect = int((mask_fn(vals) & (ts >= t0) & (ts <= t1)).sum())
    assert count == expect
    assert (top_ts >= t0).all() and (top_ts <= t1).all()


@pytest.mark.parametrize(
    "spec",
    [
        AggregateSpec(group_by=("status",), time_bucket_s=3600),
        AggregateSpec(group_by=("domain", "method")),
        AggregateSpec(group_by=("domain",), op="sum", value_field="bytes_out"),
        AggregateSpec(group_by=("status",), op="max", value_field="bytes_out"),
    ],
)
def test_device_ingest_aggregate_matches_host(ingested, spec):
    """Exact-agreement oracle: same events in -> identical aggregates out
    of the host iterator stack and the device plane."""
    store, _, dq, _, _ = ingested
    tree = Eq("domain", "a.com")
    host = QueryProcessor(store).aggregate(spec, 0, T_SPAN, tree)
    dist = dq.aggregate_range(spec, tree, 0, T_SPAN)

    def as_map(res):
        return {
            tuple(sorted((k, v) for k, v in r.items() if k not in ("value", "count"))): (
                r["value"], r["count"],
            )
            for r in res.rows(store)
        }

    assert as_map(host) == as_map(dist)


def test_live_incremental_visibility(ingested):
    """Rows written after the first publish become visible on the next
    query with no re-scatter (the DistStore incremental-update path)."""
    store, plane, dq, ts, vals = ingested
    tree = Eq("domain", "c.com")
    before, _, _ = dq.scan_range(tree, 0, T_SPAN)
    extra_ts = np.array([100, 200, 300])
    w = DistBatchWriter(store, plane, batch_rows=2)
    w.add(extra_ts, {"domain": ["c.com"] * 3, "method": ["GET"] * 3, "status": ["200"] * 3})
    w.close()
    after, _, _ = dq.scan_range(tree, 0, T_SPAN)
    assert after == before + 3
    # Re-publish with nothing new is a no-op (cached store view).
    assert plane.publish() is plane.publish()


def test_from_event_store_replay_matches_scatter_semantics():
    """from_event_store (now a bulk replay through the plane) yields the
    same query results as the host store, at several tablet widths."""
    ts, vals = _gen(seed=11, n=6000)
    store = EventStore(web_proxy_schema(), n_shards=4)
    store.ingest(ts, vals)
    store.flush_all()
    mesh = make_dev_mesh(1, 1)
    varr = {k: np.array(v) for k, v in vals.items()}
    expect = int((varr["domain"] == "b.com").sum())
    for tpd in (1, 3):
        dist = from_event_store(store, mesh, tablets_per_device=tpd)
        assert dist.n_tablets == tpd
        dq = DistQueryProcessor(store, dist)
        count, _, _ = dq.scan_range(Eq("domain", "b.com"), 0, T_SPAN)
        assert count == expect


# ----------------------------------------------------------- backpressure
def test_backpressure_counters_monotonic():
    """Device compaction counters and rows are monotone non-decreasing
    across flushes; blocked time only accrues when majors run."""
    ts, vals = _gen(seed=17, n=8000)
    store = EventStore(web_proxy_schema(), n_shards=4)
    mesh = make_dev_mesh(1, 1)
    plane = DistIngestPlane(
        mesh, store.schema.n_fields, capacity=10_000,
        tablets_per_device=2, mem_rows=512, max_runs=2, append_rows=256,
    )
    m = IngestMetrics()
    w = DistBatchWriter(store, plane, batch_rows=400, metrics=m)
    prev = None
    for off in range(0, len(ts), 400):
        sl = slice(off, off + 400)
        w.add(ts[sl], {k: v[sl] for k, v in vals.items()})
        tel = plane.telemetry()
        cur = (
            int(tel["rows"].sum()), int(tel["minor"].sum()),
            int(tel["major"].sum()), float(tel["blocked_seconds"]),
        )
        if prev is not None:
            assert all(a >= b for a, b in zip(cur, prev)), (cur, prev)
        prev = cur
    w.close()
    tel = plane.telemetry()
    assert int(tel["rows"].sum()) == len(ts)
    assert int(tel["overflow"].sum()) == 0
    # Tiny memtables + tiny max_runs: majors must have fired and blocked.
    assert int(tel["major"].sum()) >= 1
    assert m.blocked_seconds > 0
    assert m.rows == len(ts)


def test_tablet_guidance():
    assert check_tablet_guidance(4, 8)
    assert not check_tablet_guidance(3, 8)


def test_concurrent_writers_threaded():
    """Several DistBatchWriters flushing from real threads: the plane lock
    must keep every row accounted and the memtables consistent."""
    import threading

    ts, vals = _gen(seed=31, n=6000)
    store = EventStore(web_proxy_schema(), n_shards=2)
    mesh = make_dev_mesh(1, 1)
    plane = DistIngestPlane(
        mesh, store.schema.n_fields, capacity=8000,
        tablets_per_device=2, mem_rows=512, max_runs=2, append_rows=256,
    )
    n_w = 3
    per = len(ts) // n_w

    def work(i):
        w = DistBatchWriter(store, plane, batch_rows=333, writer_id=i)
        sl = slice(i * per, (i + 1) * per)
        for off in range(0, per, 333):
            s2 = slice(sl.start + off, min(sl.start + off + 333, sl.stop))
            w.add(ts[s2], {k: v[s2] for k, v in vals.items()})
        w.close()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_w)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tel = plane.telemetry()
    assert int(tel["rows"].sum()) == n_w * per
    assert int(tel["overflow"].sum()) == 0
    dq = DistQueryProcessor(store, plane=plane)
    total, _, _ = dq.scan_range(None, 0, T_SPAN)
    assert total == n_w * per


def test_large_value_sum_agreement():
    """Sums of large numeric values must not wrap int32 anywhere: host
    iterator stack, combine_scan backends, and the device plane agree."""
    rng = np.random.default_rng(41)
    n = 3000
    ts = np.sort(rng.integers(0, 3600, n))
    vals = {
        "domain": ["a.com"] * n,
        "method": ["GET"] * n,
        "status": ["200"] * n,
        # ~2e9 per row: three rows already exceed int32.
        "bytes_out": rng.integers(1_900_000_000, 2_000_000_000, size=n).astype(str).tolist(),
    }
    store = EventStore(web_proxy_schema(), n_shards=2)
    store.ingest(ts, vals)
    store.flush_all()
    spec = AggregateSpec(group_by=("domain",), op="sum", value_field="bytes_out")
    host = QueryProcessor(store).aggregate(spec, 0, 3600 * 2)
    [row] = host.rows(store)
    assert row["value"] > np.iinfo(np.int32).max  # really exercised the widening
    mesh = make_dev_mesh(1, 1)
    dist = from_event_store(store, mesh)
    d = DistQueryProcessor(store, dist).aggregate_range(spec, None, 0, 3600 * 2)
    [drow] = d.rows(store)
    assert drow["value"] == row["value"] and drow["count"] == row["count"]


def test_writer_rejects_out_of_range_timestamps():
    """Same 30-bit contract as EventStore.ingest_encoded — raw unix-epoch
    seconds must fail loudly, not wrap into negative rev_ts."""
    from repro.core import keypack

    store = EventStore(web_proxy_schema(), n_shards=1)
    mesh = make_dev_mesh(1, 1)
    plane = DistIngestPlane(mesh, store.schema.n_fields, capacity=64)
    w = DistBatchWriter(store, plane, batch_rows=1)
    with pytest.raises(ValueError, match="30-bit"):
        w.add(
            np.array([keypack.TS_MAX + 1]),
            {"domain": ["a.com"], "method": ["GET"], "status": ["200"]},
        )


def test_from_event_store_undersized_capacity_raises():
    """Explicit undersized capacity must fail loudly (the pre-plane
    scatter's contract), not silently drop rows into the overflow counter."""
    ts, vals = _gen(seed=23, n=2000)
    store = EventStore(web_proxy_schema(), n_shards=2)
    store.ingest(ts, vals)
    store.flush_all()
    mesh = make_dev_mesh(1, 1)
    with pytest.raises(ValueError, match="overflow"):
        from_event_store(store, mesh, capacity=500)


def test_published_store_survives_later_compactions():
    """A published DistStore view must stay valid (buffers not donated)
    after further ingest trips minor/major compactions."""
    ts, vals = _gen(seed=29, n=4000)
    store = EventStore(web_proxy_schema(), n_shards=2)
    mesh = make_dev_mesh(1, 1)
    plane = DistIngestPlane(
        mesh, store.schema.n_fields, capacity=10_000, mem_rows=512, max_runs=2, append_rows=256,
    )
    w = DistBatchWriter(store, plane, batch_rows=500)
    w.add(ts[:2000], {k: v[:2000] for k, v in vals.items()})
    w.close()
    ds = plane.publish()
    counts_before = np.asarray(jax.device_get(ds.counts)).copy()
    w.add(ts[2000:], {k: v[2000:] for k, v in vals.items()})
    w.close()
    plane.publish()
    # The old view still reads, and still shows the old counts.
    np.testing.assert_array_equal(np.asarray(jax.device_get(ds.counts)), counts_before)


def test_plane_state_keeps_its_layout_through_compactions():
    """Every program hands the state back with the shapes, dtypes and
    shardings it was created with: a drift (an int64 live count after a
    major, a canonicalised sharding) would make the next call of every
    step compile again, inside ingest."""
    store = EventStore(web_proxy_schema())
    plane = DistIngestPlane.for_store(
        store, make_dev_mesh(1, 1), capacity=1 << 12, tablets_per_device=4,
        mem_rows=64, max_runs=2,
    )
    g = plane.groups[0]
    layout = g.programs.state_layout()
    created = {k: v.sharding for k, v in g.state.items()}
    rng = np.random.default_rng(0)
    n = 4 * 64 * 4
    plane.ingest(
        rng.integers(0, 1 << 29, n).astype(np.int32),
        rng.integers(0, 9, (n, 12)).astype(np.int32),
        rng.integers(0, 4, n).astype(np.int32),
    )
    assert int(g._major_host.sum()) > 0
    plane.compact_step()
    plane.publish()
    plane.compact()
    for k, v in g.state.items():
        assert (v.shape, v.dtype) == (layout[k][0], layout[k][1]), k
        assert v.sharding == created[k], k
