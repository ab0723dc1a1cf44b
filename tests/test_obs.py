"""Observability plane: registry semantics vs numpy oracles, span
nesting/parent integrity under the concurrent serve harness, occupancy
attribution summing to lock-held time, the disabled-mode overhead gate,
and Chrome-trace schema validation."""
import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import EventStore, Eq, web_proxy_schema
from repro.core.dist_ingest import DistBatchWriter, DistIngestPlane
from repro.core.dist_query import DistQueryProcessor
from repro.core.ingest import BatchWriter, IngestMetrics, rate_series
from repro.launch.mesh import make_dev_mesh
from repro.obs.registry import MetricsRegistry
from repro.serve_db import QueryService

T_SPAN = 2 * 3600


# ---------------------------------------------------------------- registry
def test_counter_label_semantics():
    reg = MetricsRegistry("t_counter")
    c = reg.counter("rows")
    rng = np.random.default_rng(0)
    per = {}
    for _ in range(500):
        w = int(rng.integers(0, 5))
        v = float(rng.integers(1, 100))
        c.inc(v, writer=w)
        per[w] = per.get(w, 0.0) + v
    for w, total in per.items():
        assert c.value(writer=w) == total
    assert c.total() == pytest.approx(sum(per.values()))
    # reset of one label leaves the others
    c.reset(writer=0)
    assert c.value(writer=0) == 0.0
    assert c.value(writer=1) == per.get(1, 0.0)


def test_counter_threaded_total():
    reg = MetricsRegistry("t_threads")
    c = reg.counter("hits")

    def work(tid):
        for _ in range(2000):
            c.inc(1, thread=tid)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.total() == 8000


def test_histogram_vs_numpy_oracle():
    reg = MetricsRegistry("t_hist")
    edges = [0.001, 0.01, 0.1, 1.0]
    h = reg.histogram("lat", edges=edges)
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=-4, sigma=2.0, size=2000)
    for v in vals:
        h.observe(float(v))
    snap = h.snapshot()
    # Oracle: np.histogram over (-inf, e0], (e0, e1], ..., (e_last, inf)
    oracle, _ = np.histogram(vals, bins=[-np.inf] + edges + [np.inf])
    assert snap["buckets"] == oracle.tolist()
    assert snap["count"] == len(vals)
    assert snap["sum"] == pytest.approx(vals.sum(), rel=1e-9)
    assert snap["min"] == pytest.approx(vals.min())
    assert snap["max"] == pytest.approx(vals.max())


def test_histogram_bucket_edge_exact():
    """A value exactly on an edge lands in the bucket that edge closes
    (half-open on the left), deterministically."""
    reg = MetricsRegistry("t_edge")
    h = reg.histogram("x", edges=[1.0, 2.0])
    for _ in range(10):
        h.observe(1.0)
    snap = h.snapshot()
    assert snap["buckets"] == [10, 0, 0]
    assert snap["count"] == 10


def test_registry_disabled_is_noop():
    reg = MetricsRegistry("t_disabled", enabled=False)
    c = reg.counter("n")
    h = reg.histogram("h")
    c.inc(5)
    h.observe(1.0)
    assert c.total() == 0.0
    assert h.count() == 0


def test_metric_kind_collision_raises():
    reg = MetricsRegistry("t_kind")
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


# ------------------------------------------------------------- IngestMetrics
def test_ingest_metrics_is_registry_view():
    m = IngestMetrics()
    m.rows += 100
    m.rows += 50
    m.blocked_seconds += 0.25
    assert m.rows == 150
    assert m.blocked_seconds == pytest.approx(0.25)
    # The same cells are visible on the default registry, per-writer.
    reg = obs.get_registry()
    c = reg.get("ingest_rows_total")
    assert c is not None and c.value(writer=m._label) == 150
    # Independent instances never share cells.
    m2 = IngestMetrics()
    assert m2.rows == 0
    m2.rows = 7
    assert m.rows == 150 and m2.rows == 7


# --------------------------------------------------------------- rate_series
def test_rate_series_conserves_rows():
    m = IngestMetrics()
    rng = np.random.default_rng(3)
    t0 = 1000.0
    for i in range(200):
        m.samples.append((t0 + float(rng.uniform(0, 10)), int(rng.integers(1, 500))))
    m.samples.sort()
    for bucket in (0.25, 0.5, 1.0):
        xs, rate = rate_series([m], bucket_s=bucket)
        total = sum(s[1] for s in m.samples)
        assert rate.sum() * bucket == pytest.approx(total)
        assert len(xs) == len(rate)


def test_rate_series_boundary_not_double_counted():
    """Events exactly on bucket boundaries land in exactly one bucket:
    totals conserve and the bucket assignment is the half-open one."""
    m = IngestMetrics()
    t0 = 50.0
    bucket = 0.25
    # Samples exactly on edges 0, 1, 2, ... of the bucket grid.
    for i in range(8):
        m.samples.append((t0 + i * bucket, 100))
    xs, rate = rate_series([m], bucket_s=bucket)
    assert rate.sum() * bucket == pytest.approx(800)
    # Each on-edge event opens its own bucket: one event per bucket.
    assert np.allclose(rate[: len(rate) - 1], 100 / bucket) or rate.max() * bucket == 100


def test_rate_series_empty():
    xs, rate = rate_series([IngestMetrics()])
    assert len(xs) == 0 and len(rate) == 0


# ----------------------------------------------------------------- OwnedLock
def test_owned_lock_partitions_held_time():
    lk = obs.OwnedLock("t_lock")
    with lk.hold("a"):
        time.sleep(0.02)
        with lk.reowner("b"):
            time.sleep(0.03)
        time.sleep(0.01)
    with lk.hold("c"):
        time.sleep(0.01)
    snap = lk.snapshot()
    by = snap["by_owner_s"]
    assert set(by) == {"a", "b", "c"}
    # Books balance exactly: per-owner segments partition each hold.
    assert sum(by.values()) == pytest.approx(snap["total_held_s"], rel=1e-9)
    assert by["b"] >= 0.025  # the re-owned stretch is charged to b
    assert snap["acquisitions"] == 2


def test_owned_lock_plain_with_is_unknown():
    lk = obs.OwnedLock("t_lock_plain")
    with lk:
        pass
    assert "unknown" in lk.snapshot()["by_owner_s"]


def test_owned_lock_nonblocking_contention():
    lk = obs.OwnedLock("t_lock_nb")
    assert lk.acquire(blocking=False, owner="x")
    assert not lk.acquire(blocking=False, owner="y")
    lk.release()
    snap = lk.snapshot()
    assert snap["acquisitions"] == 1
    assert "y" not in snap["by_owner_s"]


# ------------------------------------------------------------------- tracing
def test_span_nesting_and_parent_linkage():
    obs.enable()
    obs.clear()
    try:
        with obs.span("outer", cat="t") as so:
            with obs.span("inner", cat="t") as si:
                pass
        with obs.span("sibling", cat="t"):
            pass
    finally:
        obs.disable()
    recs = {r["name"]: r for r in obs.get_tracer().records}
    assert recs["inner"]["parent"] == recs["outer"]["sid"]
    assert recs["sibling"]["parent"] == 0
    assert recs["outer"]["parent"] == 0
    # Parent interval contains the child (same thread, same clock).
    o, i = recs["outer"], recs["inner"]
    assert o["t0"] <= i["t0"] and i["t0"] + i["dur"] <= o["t0"] + o["dur"] + 1e-6
    assert o["tid"] == i["tid"]


def test_traced_decorator_and_args():
    obs.enable()
    obs.clear()
    try:

        @obs.traced("deco.fn", cat="t")
        def fn(x):
            return x + 1

        assert fn(1) == 2
        with obs.span("with_args", cat="t", k=3) as sp:
            sp.set(result=9)
    finally:
        obs.disable()
    recs = {r["name"]: r for r in obs.get_tracer().records}
    assert "deco.fn" in recs
    assert recs["with_args"]["args"] == {"k": 3, "result": 9}


def test_chrome_trace_schema():
    obs.enable()
    obs.clear()
    try:
        with obs.span("a", cat="t"):
            with obs.span("b", cat="t"):
                pass
    finally:
        obs.disable()
    doc = obs.chrome_trace()
    # Round-trips through JSON and passes the shared validator.
    doc2 = json.loads(json.dumps(doc))
    assert obs.validate_chrome_trace(doc2) == []
    xs = [e for e in doc2["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a", "b"}
    b = next(e for e in xs if e["name"] == "b")
    a = next(e for e in xs if e["name"] == "a")
    assert b["args"]["parent"] == a["args"]["sid"]


def test_chrome_trace_validator_catches_problems():
    assert obs.validate_chrome_trace({}) != []
    assert obs.validate_chrome_trace({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0.0, "dur": -1.0}]}
    assert any("negative" in p for p in obs.validate_chrome_trace(bad))
    orphan = {
        "traceEvents": [
            {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0.0, "dur": 1.0,
             "args": {"sid": 1, "parent": 99}}
        ]
    }
    assert any("parent" in p for p in obs.validate_chrome_trace(orphan))


def test_metrics_snapshot_and_summary():
    reg = MetricsRegistry("t_snapshot")
    reg.counter("snap_rows").inc(42, writer="w")
    reg.histogram("snap_lat").observe(0.005)
    snap = obs.metrics_snapshot()
    assert snap["schema_version"] == 1
    assert "t_snapshot" in snap["registries"]
    cells = snap["registries"]["t_snapshot"]["snap_rows"]["cells"]
    assert cells == {"writer=w": 42.0}
    json.dumps(snap)  # JSON-serializable end to end
    text = obs.summary()
    assert "snap_rows" in text and "snap_lat" in text


# ------------------------------------------- serve harness: spans + occupancy
def _serve_fixture(n=4_000):
    rng = np.random.default_rng(11)
    ts = np.sort(rng.integers(0, T_SPAN, n))
    vals = {
        "domain": rng.choice(
            ["a.com", "b.com", "c.com", "rare.net"], p=[0.6, 0.25, 0.13, 0.02], size=n
        ).tolist(),
        "method": rng.choice(["GET", "POST"], size=n).tolist(),
        "status": rng.choice(["200", "404"], size=n, p=[0.8, 0.2]).tolist(),
    }
    store = EventStore(web_proxy_schema(), n_shards=4)
    store.ingest(ts, vals)
    store.flush_all()
    store.compact_all()
    plane = DistIngestPlane.for_store(
        store, make_dev_mesh(1, 1), capacity=2 * n, tablets_per_device=2,
        mem_rows=512, max_runs=4, append_rows=256,
    )
    w = DistBatchWriter(store, plane, batch_rows=1024)
    w.add(ts, {k: list(v) for k, v in vals.items()})
    w.close()
    return store, plane


def test_serve_spans_and_occupancy_under_4_sessions():
    store, plane = _serve_fixture()
    obs.enable()
    obs.clear()
    try:
        with QueryService(store, plane, compaction_interval=0.01) as svc:
            sessions = [svc.session(name=f"s{i}") for i in range(4)]
            streams = []
            for i, s in enumerate(sessions):
                tree = Eq("domain", ["a.com", "b.com", "c.com", "rare.net"][i])
                streams.append(s.submit("batched_index", 0, T_SPAN, tree))
                streams.append(s.submit("batched_scan", 0, T_SPAN, None))
            for sq in streams:
                for _ in sq.results():
                    pass
            occ = svc._device_lock.snapshot()
    finally:
        obs.disable()

    # --- span integrity ---------------------------------------------------
    recs = list(obs.get_tracer().records)
    by_sid = {r["sid"]: r for r in recs}
    names = {r["name"] for r in recs}
    assert "serve.turn" in names and "query.step" in names and "query.plan" in names
    for r in recs:
        if r["parent"]:
            assert r["parent"] in by_sid, f"orphan parent for {r['name']}"
            p = by_sid[r["parent"]]
            assert p["tid"] == r["tid"]
            # Parent interval contains the child (small epsilon: both
            # timestamps come from the same perf_counter clock).
            assert p["t0"] - 1e-6 <= r["t0"]
            assert r["t0"] + r["dur"] <= p["t0"] + p["dur"] + 1e-6
    # Every query.step under serving hangs off a serve.turn ancestor.
    steps = [r for r in recs if r["name"] == "query.step"]
    assert steps

    def has_turn_ancestor(r):
        while r["parent"]:
            r = by_sid[r["parent"]]
            if r["name"] == "serve.turn":
                return True
        return False

    assert all(has_turn_ancestor(r) for r in steps)

    # --- occupancy --------------------------------------------------------
    by = occ["by_owner_s"]
    assert "unknown" not in by
    assert "session_turn" in by and "density_read" in by
    assert set(by) <= {"session_turn", "density_read", "fold_increment"}
    assert sum(by.values()) == pytest.approx(occ["total_held_s"], rel=1e-6)
    # Plane lock: fully attributed too (appends, publishes, folds...).
    pocc = plane._lock.snapshot()
    assert "unknown" not in pocc["by_owner_s"]
    assert sum(pocc["by_owner_s"].values()) == pytest.approx(
        pocc["total_held_s"], rel=1e-6
    )
    # Trace exports cleanly after the run.
    assert obs.validate_chrome_trace(obs.chrome_trace()) == []


def test_fold_attribution_still_exact():
    """The registry migration must not change fold_events semantics: the
    query path never folds, sources are the known set."""
    store, plane = _serve_fixture(n=2_000)
    plane.compact(source="explicit")
    dq = DistQueryProcessor(store, plane=plane)
    dq.scan_range(None, 0, T_SPAN)
    fe = plane.telemetry()["fold_events"]
    assert set(fe) <= {"ingest", "background", "explicit"}
    assert fe.get("explicit", 0) >= 1


# -------------------------------------------------------- overhead gate (<2%)
def test_disabled_tracing_overhead_under_2pct():
    """The acceptance gate: with tracing disabled, the per-span cost on
    the query path must be < 2% of a scan microbench step. Measured
    directly: (disabled span cost x spans-per-scan) vs median scan
    time."""
    store, plane = _serve_fixture(n=2_000)
    dq = DistQueryProcessor(store, plane=plane)
    assert not obs.enabled()
    dq.scan_range(None, 0, T_SPAN)  # warm compiles
    scan_times = []
    for _ in range(10):
        t0 = time.perf_counter()
        dq.scan_range(None, 0, T_SPAN)
        scan_times.append(time.perf_counter() - t0)
    scan_s = float(np.median(scan_times))

    n_iter = 100_000
    t0 = time.perf_counter()
    for _ in range(n_iter):
        with obs.span("x", cat="t"):
            pass
    span_s = (time.perf_counter() - t0) / n_iter
    # A scan_range call opens O(1) spans; allow ten for headroom.
    overhead = 10 * span_s / scan_s
    assert overhead < 0.02, f"disabled-span overhead {overhead:.4%} of a scan"


# ------------------------------------------------------------- span sampling
def test_span_sampling_keeps_every_nth_root_with_children():
    obs.clear()
    obs.enable(sample=1 / 3)
    try:
        for i in range(9):
            with obs.span(f"root{i}", cat="t"):
                with obs.span(f"child{i}", cat="t"):
                    pass
    finally:
        obs.disable()
        assert obs.get_tracer().sample_n == 1  # disable resets the knob
    names = [r["name"] for r in obs.get_tracer().records]
    # Roots 1, 4, 7 (1-based counter % 3 == 1) survive, each with its
    # child; children exit first so they precede their root on record.
    assert names == ["child0", "root0", "child3", "root3", "child6", "root6"]
    recs = {r["name"]: r for r in obs.get_tracer().records}
    for i in (0, 3, 6):
        assert recs[f"child{i}"]["parent"] == recs[f"root{i}"]["sid"]
    obs.clear()


def test_span_sampling_dropped_root_children_follow():
    """A child under a dropped root is dropped even if the tree is deep,
    and a dropped span's fence/set are pass-through no-ops."""
    obs.clear()
    obs.enable(sample=1 / 2)  # keeps roots 1, 3, ... drops 2, 4, ...
    try:
        with obs.span("kept", cat="t"):
            pass
        with obs.span("dropped", cat="t") as sp:
            assert sp.fence(41) == 41
            sp.set(ignored=True)
            with obs.span("d.child", cat="t"):
                with obs.span("d.grandchild", cat="t"):
                    pass
        # After the dropped tree closes, sampling resumes normally.
        with obs.span("kept2", cat="t"):
            pass
    finally:
        obs.disable()
    names = [r["name"] for r in obs.get_tracer().records]
    assert names == ["kept", "kept2"]
    obs.clear()


def test_span_sampling_full_rate_unchanged():
    """enable(sample=1.0) and plain enable() keep every span (the default
    path stays byte-identical in behavior)."""
    for kwargs in ({}, {"sample": 1.0}, {"sample": None}):
        obs.clear()
        obs.enable(**kwargs)
        try:
            with obs.span("a", cat="t"):
                with obs.span("b", cat="t"):
                    pass
        finally:
            obs.disable()
        assert {r["name"] for r in obs.get_tracer().records} == {"a", "b"}
    with pytest.raises(ValueError):
        obs.enable(sample=-0.5)
    obs.disable()
    obs.clear()


def test_sampled_out_span_overhead_gate():
    """The sampling companion to the disabled gate: a sampled-OUT span
    must stay within the same cheap-singleton cost class — no record
    append, no sid allocation, just a thread-local depth touch."""
    obs.clear()
    obs.enable(sample=1 / 100_000)
    try:
        n_iter = 50_000
        t0 = time.perf_counter()
        for _ in range(n_iter):
            with obs.span("x", cat="t"):
                pass
        per_span = (time.perf_counter() - t0) / n_iter
    finally:
        obs.disable()
    # Only the first root of the period was kept.
    assert len(obs.get_tracer().records) == 1
    assert per_span < 50e-6, f"sampled-out span cost {per_span * 1e6:.1f}us"
    obs.clear()


# ----------------------------------------------------- Prometheus exposition
def _parse_prom(text):
    """Tiny exposition-format parser: name -> {"type": ..., "samples":
    {(sample_name, frozenset(labels.items())): value}}."""
    import re

    out = {}
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            out.setdefault(name, {"type": kind, "samples": {}})
            continue
        if line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z0-9_:]+)(\{(.*)\})? (\S+)$", line)
        assert m, f"unparseable sample line: {line!r}"
        sname, _, labelstr, val = m.groups()
        labels = {}
        if labelstr:
            for part in re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', labelstr):
                labels[part[0]] = part[1].replace('\\"', '"').replace("\\\\", "\\")
        family = next((t for t in types if sname.startswith(t)), sname)
        out.setdefault(family, {"type": types.get(family), "samples": {}})
        fval = float("inf") if val == "+Inf" else float(val)
        out[family]["samples"][(sname, frozenset(labels.items()))] = fval
    return out


def test_prometheus_text_roundtrip():
    reg = MetricsRegistry("t_prom")
    c = reg.counter("prom_rows_total", "rows ingested")
    c.inc(5, writer="3")
    c.inc(2.5, writer="7")
    g = reg.gauge("prom_fill", "memtable fill fraction")
    g.set(0.5)
    h = reg.histogram("prom_lat_seconds", "latency", edges=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0, 0.05):
        h.observe(v, op="scan")

    doc = _parse_prom(obs.to_prometheus_text(reg))

    assert doc["prom_rows_total"]["type"] == "counter"
    s = doc["prom_rows_total"]["samples"]
    assert s[("prom_rows_total", frozenset({("writer", "3")}))] == 5.0
    assert s[("prom_rows_total", frozenset({("writer", "7")}))] == 2.5

    assert doc["prom_fill"]["type"] == "gauge"
    assert doc["prom_fill"]["samples"][("prom_fill", frozenset())] == 0.5

    assert doc["prom_lat_seconds"]["type"] == "histogram"
    hs = doc["prom_lat_seconds"]["samples"]

    def bucket(le):
        return hs[("prom_lat_seconds_bucket", frozenset({("op", "scan"), ("le", le)}))]

    # Cumulative buckets, exact against the observations above.
    assert bucket("0.01") == 1
    assert bucket("0.1") == 3
    assert bucket("1") == 4
    assert bucket("+Inf") == 5
    assert hs[("prom_lat_seconds_count", frozenset({("op", "scan")}))] == 5
    assert hs[("prom_lat_seconds_sum", frozenset({("op", "scan")}))] == pytest.approx(
        5.605
    )


def test_prometheus_text_escaping_and_empty():
    reg = MetricsRegistry("t_prom_esc")
    assert obs.to_prometheus_text(reg) == ""
    c = reg.counter("esc_total", 'help with "quotes"')
    c.inc(1, path='a"b\\c')
    text = obs.to_prometheus_text(reg)
    assert '# HELP esc_total help with \\"quotes\\"' in text
    doc = _parse_prom(text)
    assert doc["esc_total"]["samples"][
        ("esc_total", frozenset({("path", 'a"b\\c')}))
    ] == 1.0


def test_prometheus_text_all_registries_dedupes_names():
    a = MetricsRegistry("t_prom_a")
    b = MetricsRegistry("t_prom_b")
    a.counter("dup_total").inc(1)
    b.counter("dup_total").inc(100)
    text = obs.to_prometheus_text()
    assert text.count("# TYPE dup_total counter") == 1


# ----------------------------------------------------------------- exporters
def test_write_exporters_roundtrip(tmp_path):
    obs.enable()
    obs.clear()
    try:
        with obs.span("io", cat="t"):
            pass
    finally:
        obs.disable()
    tpath = tmp_path / "trace.json"
    mpath = tmp_path / "metrics.json"
    obs.write_chrome_trace(str(tpath))
    obs.write_metrics_json(str(mpath))
    tdoc = json.loads(tpath.read_text())
    mdoc = json.loads(mpath.read_text())
    assert obs.validate_chrome_trace(tdoc) == []
    assert mdoc["schema_version"] == 1
    assert "lock_occupancy" in mdoc


# ----------------------------------------------------- Prometheus endpoint
def test_serve_prometheus_start_scrape_stop():
    """The pull endpoint serves the exposition text at /metrics on an
    ephemeral port, 404s other paths, and stops cleanly (twice over:
    explicit stop and context manager)."""
    from urllib.error import HTTPError
    from urllib.request import urlopen

    reg = MetricsRegistry("t_prom_http")
    reg.counter("scrapes_total", "scrapes").inc(3, path="/metrics")
    ep = obs.serve_prometheus(reg)
    try:
        assert ep.port > 0
        body = urlopen(ep.url, timeout=5).read().decode()
        assert body == obs.to_prometheus_text(reg)
        assert 'scrapes_total{path="/metrics"} 3' in body
        with pytest.raises(HTTPError) as exc:
            urlopen(f"http://{ep.host}:{ep.port}/other", timeout=5)
        assert exc.value.code == 404
    finally:
        ep.stop()
    with pytest.raises(OSError):
        urlopen(f"http://{ep.host}:{ep.port}/metrics", timeout=1)
    with obs.serve_prometheus(reg) as ep2:
        assert urlopen(ep2.url, timeout=5).status == 200


# ------------------------------------------------------- lock wait accounting
def test_owned_lock_books_acquire_wait():
    """total_wait_s/wait_by_owner_s accumulate the time a would-be holder
    spent inside acquire(): a sole acquirer books ~zero wait, a thread
    blocked behind a deliberate hold books at least the hold time."""
    lk = obs.OwnedLock("t_wait_lock")
    with lk.hold("solo"):
        pass
    solo = lk.snapshot()
    assert solo["total_wait_s"] < 0.05  # uncontended: microseconds
    hold_s = 0.15
    started = threading.Event()

    def holder():
        with lk.hold("hog"):
            started.set()
            time.sleep(hold_s)

    t = threading.Thread(target=holder)
    t.start()
    started.wait()
    with lk.hold("waiter"):
        pass
    t.join()
    snap = lk.snapshot()
    assert snap["wait_by_owner_s"]["waiter"] > hold_s / 2
    assert abs(
        sum(snap["wait_by_owner_s"].values()) - snap["total_wait_s"]
    ) < 1e-9
    # Merged report carries the same keys; reset clears them.
    merged = obs.occupancy_snapshot()["t_wait_lock"]
    assert merged["total_wait_s"] == snap["total_wait_s"]
    lk.reset()
    assert lk.snapshot()["total_wait_s"] == 0.0


# ------------------------------------------------------------ flight recorder
def test_flight_ring_wraparound_evicts_oldest():
    """A private recorder with an 8-slot ring keeps exactly the 8 newest
    spans; older records are overwritten in place and sids never repeat."""
    fr = obs.FlightRecorder(per_thread=8)
    for i in range(20):
        with fr.span(f"s{i}", cat="t"):
            pass
    recs = fr.records()
    assert [r["name"] for r in recs] == [f"s{i}" for i in range(12, 20)]
    assert len({r["sid"] for r in recs}) == 8
    # Flight sids live in their own namespace, far above tracer sids.
    assert all(r["sid"] >= (1 << 40) for r in recs)


def test_flight_dump_roundtrips_validator_with_evicted_parents():
    """dump() must validate even when the ring evicted (or has not yet
    recorded) a kept child's parent: the dangling parent ref is cleared.
    Once the parent record lands, kept children link to it again."""
    fr = obs.FlightRecorder(per_thread=4)
    with fr.span("root", cat="t"):
        for i in range(6):
            with fr.span(f"c{i}", cat="t"):
                pass
        # Root is still open -> not recorded -> every kept child's parent
        # points outside the dump. The dump must clear those refs.
        mid = fr.dump(window_s=60.0)
        assert obs.validate_chrome_trace(mid) == []
        xs = [e for e in mid["traceEvents"] if e.get("ph") == "X"]
        assert [e["name"] for e in xs] == ["c2", "c3", "c4", "c5"]
        assert all("parent" not in e["args"] for e in xs)
    doc = fr.dump(window_s=60.0)
    assert obs.validate_chrome_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    # records() orders by span START, so the long-open root sorts first
    assert [e["name"] for e in xs] == ["root", "c3", "c4", "c5"]
    root_sid = next(e["args"]["sid"] for e in xs if e["name"] == "root")
    for e in xs:
        if e["name"] != "root":
            assert e["args"]["parent"] == root_sid
    # Thread metadata rides along for Perfetto lane names.
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])


def test_flight_captures_serve_plane_with_tracing_disabled():
    """The acceptance shape: tracing OFF for the whole run, flight ON —
    the dump still covers ingest, fold, and serve spans and validates."""
    assert not obs.enabled()
    obs.flight_clear()
    obs.flight_enable()
    try:
        store, plane = _serve_fixture(n=2_000)
        plane.compact(source="explicit")
        with QueryService(store, plane, compaction_interval=0.01) as svc:
            s = svc.session("flight0")
            s.submit("batched_index", 0, T_SPAN, Eq("domain", "a.com")).drain(
                timeout=120.0
            )
        doc = obs.flight_dump(window_s=600.0)
    finally:
        obs.flight_disable()
        obs.flight_clear()
    assert obs.validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert "serve.turn" in names
    assert any(n.startswith("ingest.") for n in names)
    assert "ingest.compact" in names  # the fold path
    assert any(n.startswith("query.") for n in names)


def test_flight_enabled_overhead_under_2pct():
    """Same budget as the disabled-tracing gate: with the flight recorder
    armed (tracing still off), per-span cost stays < 2% of a scan step."""
    store, plane = _serve_fixture(n=2_000)
    dq = DistQueryProcessor(store, plane=plane)
    assert not obs.enabled()
    dq.scan_range(None, 0, T_SPAN)  # warm compiles
    scan_times = []
    for _ in range(10):
        t0 = time.perf_counter()
        dq.scan_range(None, 0, T_SPAN)
        scan_times.append(time.perf_counter() - t0)
    scan_s = float(np.median(scan_times))

    obs.flight_clear()
    obs.flight_enable()
    try:
        n_iter = 100_000
        t0 = time.perf_counter()
        for _ in range(n_iter):
            with obs.span("x", cat="t"):
                pass
        span_s = (time.perf_counter() - t0) / n_iter
    finally:
        obs.flight_disable()
        obs.flight_clear()
    overhead = 10 * span_s / scan_s
    assert overhead < 0.02, f"flight-span overhead {overhead:.4%} of a scan"


def test_flight_captures_sampled_out_spans():
    """With tracing sampling at 1/3, the tracer keeps every 3rd root but
    the flight window keeps ALL of them — its bound is time, not rate."""
    obs.flight_clear()
    obs.flight_enable()
    obs.clear()
    obs.enable(sample=1 / 3)
    try:
        for i in range(9):
            with obs.span(f"fr{i}", cat="t"):
                with obs.span(f"fk{i}", cat="t"):
                    pass
    finally:
        obs.disable()
    fnames = {r["name"] for r in obs.get_flight().records()}
    obs.flight_disable()
    obs.flight_clear()
    assert {f"fr{i}" for i in range(9)} <= fnames
    assert {f"fk{i}" for i in range(9)} <= fnames
    troots = [r for r in obs.get_tracer().records if r["name"].startswith("fr")]
    assert len(troots) == 3  # the sampler's view is still 1-in-3


# ----------------------------------------------------------------- watchdog
def test_watchdog_tick_writes_incident_bundle(tmp_path):
    """Synchronous tick(): below threshold -> nothing; p99 breach ->
    exactly one bundle (incident.json + validating trace.json +
    parseable metrics.json); cooldown suppresses the repeat."""
    reg = MetricsRegistry("t_wd_bundle")
    pending = []

    def probe():
        out = list(pending)
        pending.clear()
        return out

    rule = obs.WatchRule(
        "ttfr_p99", probe, 0.5, window_s=30.0, agg="p99", cooldown_s=3600.0
    )
    wd = obs.Watchdog(
        [rule], incident_dir=str(tmp_path / "inc"), registry=reg,
        flight_window_s=60.0,
    )
    obs.flight_clear()
    obs.flight_enable()
    try:
        with obs.span("incident_context", cat="t"):
            pass
        wd.tick()  # no events yet: no breach
        assert wd.incidents() == []
        pending.append((time.perf_counter(), 1.25))
        wd.tick()
    finally:
        obs.flight_disable()
        obs.flight_clear()
    incs = wd.incidents()
    assert len(incs) == 1 and incs[0]["kind"] == "incident"
    assert incs[0]["rule"] == "ttfr_p99"
    assert incs[0]["value"] == pytest.approx(1.25)

    bundle = incs[0]["bundle"]
    rec = json.loads(open(f"{bundle}/incident.json").read())
    assert rec["threshold"] == 0.5 and rec["agg"] == "p99"
    trace = json.loads(open(f"{bundle}/trace.json").read())
    assert obs.validate_chrome_trace(trace) == []
    assert any(
        e.get("name") == "incident_context" for e in trace["traceEvents"]
    )
    snap = json.loads(open(f"{bundle}/metrics.json").read())
    assert snap["kind"] == "obs_metrics_snapshot"

    # Registry surface: one incident, rule gauges populated.
    assert reg.counter("watchdog_incidents_total", "").value(rule="ttfr_p99") == 1
    assert reg.gauge("watchdog_rule_breached", "").value(rule="ttfr_p99") == 1.0

    # Cooldown: the window still holds the breach sample, but no new
    # bundle is written inside cooldown_s.
    wd.tick()
    assert len(wd.incidents()) == 1


def test_watchdog_rule_kinds_and_probe_error(tmp_path):
    """gauge/delta rule constructors breach on real metric movement, and
    a raising probe is recorded as probe_error without killing the tick."""
    reg = MetricsRegistry("t_wd_kinds")
    g = reg.gauge("stall_seconds", "worst increment")
    c = reg.counter("blocked_seconds_total", "writer blocked")

    def bad_probe():
        raise RuntimeError("probe exploded")

    wd = obs.Watchdog(
        [
            obs.gauge_rule("stall", g, 0.5, cooldown_s=3600.0),
            obs.counter_delta_rule(
                "blocked", c, 1.0, window_s=30.0, cooldown_s=3600.0
            ),
            obs.WatchRule("boom", bad_probe, 1.0, agg="gauge"),
        ],
        incident_dir=str(tmp_path / "inc"),
        registry=reg,
    )
    wd.tick()  # baseline: nothing breaches, boom errors
    assert [i["rule"] for i in wd.incidents() if i["kind"] == "probe_error"] == [
        "boom"
    ]
    assert wd.values()["stall"] == 0.0 and wd.values()["blocked"] == 0.0

    g.set(0.75)
    c.inc(5.0, writer="w0")
    wd.tick()
    fired = {i["rule"] for i in wd.incidents() if i.get("kind") == "incident"}
    assert fired == {"stall", "blocked"}
    assert wd.values()["stall"] == pytest.approx(0.75)
    assert wd.values()["blocked"] == pytest.approx(5.0)  # delta over window
    # Both bundles exist on disk with the full triple.
    for inc in wd.incidents():
        if inc.get("kind") != "incident":
            continue
        for part in ("incident.json", "trace.json", "metrics.json"):
            assert json.loads(open(f"{inc['bundle']}/{part}").read()) is not None


# ------------------------------------------------------------- query profile
def test_query_profile_breakdown_sums_to_ttfr():
    """Every served stream carries a committed QueryProfile whose six
    first-result stages tile the measured TTFR to within 5%, and the
    stage histograms carry trace-id exemplars."""
    store, plane = _serve_fixture()
    with QueryService(store, plane, compaction_interval=0.01) as svc:
        sessions = [svc.session(name=f"p{i}") for i in range(4)]
        streams = []
        for i, s in enumerate(sessions):
            tree = Eq("domain", ["a.com", "b.com", "c.com", "rare.net"][i])
            streams.append(s.submit("batched_index", 0, T_SPAN, tree))
            streams.append(s.submit("batched_scan", 0, T_SPAN, None))
        for sq in streams:
            sq.drain(timeout=120.0)
    for sq in streams:
        p = sq.profile
        assert p.committed and p.ttfr_s is not None and p.ttfr_s > 0
        stages = p.stages()
        assert set(stages) == set(
            ("admission", "plan", "density_fence", "device_step",
             "epilogue", "deliver")
        )
        assert all(v >= 0.0 for v in stages.values()), stages
        gap = abs(p.breakdown_sum_s() - p.ttfr_s)
        assert gap <= 0.05 * p.ttfr_s, (
            f"{p.scheme} q{p.qid}: stages {p.breakdown_sum_s():.6f}s vs "
            f"ttfr {p.ttfr_s:.6f}s ({gap / p.ttfr_s:.2%} off)"
        )
        # The queue sub-split never exceeds the whole admission stage.
        assert p.admission_queue_s <= p.admission_s + 1e-6
        assert p.steps_total >= 1 and p.device_total_s >= p.device_step_s

    import re

    h = obs.get_registry().histogram("query_profile_seconds", "")
    cell = h.snapshot(stage="device_step", scheme="batched_index")
    assert cell is not None and cell["count"] >= 1
    assert re.fullmatch(r"q\d+", cell["exemplar"]["trace_id"])
    th = obs.get_registry().histogram("query_profile_ttfr_seconds", "")
    tcell = th.snapshot(scheme="batched_scan")
    assert tcell is not None and re.fullmatch(r"q\d+", tcell["exemplar"]["trace_id"])


# -------------------------------------------------- /metrics under hammering
def _assert_hist_families_consistent(parsed):
    """Every histogram family in one scrape is internally consistent:
    cumulative buckets monotone in le, +Inf bucket equals _count."""
    for name, fam in parsed.items():
        if fam["type"] != "histogram":
            continue
        buckets, counts = {}, {}
        for (sname, labels), val in fam["samples"].items():
            ld = dict(labels)
            if sname.endswith("_bucket"):
                le = ld.pop("le")
                key = frozenset(ld.items())
                edge = float("inf") if le == "+Inf" else float(le)
                buckets.setdefault(key, []).append((edge, val))
            elif sname.endswith("_count"):
                counts[frozenset(ld.items())] = val
        for key, bs in buckets.items():
            bs.sort()
            vals = [v for _, v in bs]
            assert vals == sorted(vals), f"{name}: non-monotone buckets"
            assert bs[-1][0] == float("inf"), f"{name}: missing +Inf bucket"
            assert vals[-1] == counts[key], f"{name}: +Inf bucket != count"


def test_serve_prometheus_concurrent_scrapes_during_ingest():
    """Hammer /metrics from several threads while a writer feeds the live
    plane: every scrape parses, every histogram snapshot is internally
    consistent, no thread raises, and the port is released on stop()."""
    import socket
    from urllib.request import urlopen

    store, plane = _serve_fixture(n=2_000)
    ep = obs.serve_prometheus()  # all registries, incl. the live plane's
    stop = threading.Event()
    errors = []

    def writer_loop():
        w = DistBatchWriter(store, plane, batch_rows=256)
        rng = np.random.default_rng(5)
        budget = 1_800
        try:
            while not stop.is_set() and budget > 0:
                m = 128
                bts = np.sort(rng.integers(0, T_SPAN, m))
                bvals = {
                    "domain": rng.choice(
                        ["a.com", "b.com", "c.com", "rare.net"],
                        p=[0.6, 0.25, 0.13, 0.02], size=m,
                    ).tolist(),
                    "method": rng.choice(["GET", "POST"], size=m).tolist(),
                    "status": rng.choice(
                        ["200", "404"], size=m, p=[0.8, 0.2]
                    ).tolist(),
                }
                w.add(bts, bvals)
                budget -= m
        except Exception as e:  # surfaced below; must not die silently
            errors.append(e)
        finally:
            w.close()

    scrapes = [0] * 4

    def scrape_loop(i):
        deadline = time.perf_counter() + 1.2
        try:
            while time.perf_counter() < deadline:
                body = urlopen(ep.url, timeout=10).read().decode()
                _assert_hist_families_consistent(_parse_prom(body))
                scrapes[i] += 1
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=writer_loop)] + [
        threading.Thread(target=scrape_loop, args=(i,)) for i in range(4)
    ]
    try:
        for t in threads:
            t.start()
    finally:
        for t in threads[1:]:
            t.join()
        stop.set()
        threads[0].join()
        ep.stop()
    assert not errors, errors
    assert all(n > 0 for n in scrapes)
    # Port fully released: a fresh socket can bind it immediately.
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        s.bind((ep.host, ep.port))
    finally:
        s.close()


# --------------------------------------------------------------- daemon smoke
def test_serve_daemon_main_produces_incident(tmp_path, capsys):
    """`python -m repro.serve_db` end to end, in-process: a tight TTFR
    SLO must yield exit 0, the machine-readable header lines, and a
    validating incident bundle."""
    from repro.serve_db.__main__ import main

    try:
        rc = main(
            [
                "--rows", "1200", "--sessions", "2", "--writers", "1",
                "--duration", "1.5", "--incident-dir", str(tmp_path / "inc"),
                "--ttfr-slo", "0.000001", "--window", "5", "--tick", "0.1",
                "--groups", "1", "--tablets-per-device", "2",
            ]
        )
    finally:
        obs.flight_disable()  # main() arms the global recorder
        obs.flight_clear()
    assert rc == 0
    out = capsys.readouterr().out
    assert "METRICS_URL=http://" in out
    assert f"INCIDENT_DIR={tmp_path / 'inc'}" in out
    bundles = sorted((tmp_path / "inc").glob("*_ttfr_p99"))
    assert bundles, out
    trace = json.loads((bundles[0] / "trace.json").read_text())
    assert obs.validate_chrome_trace(trace) == []
    assert any(e.get("ph") == "X" for e in trace["traceEvents"])
    snap = json.loads((bundles[0] / "metrics.json").read_text())
    assert snap["kind"] == "obs_metrics_snapshot"
    assert "INCIDENT=" in out


def test_serve_daemon_main_fails_when_a_thread_dies(tmp_path, capsys, monkeypatch):
    """A writer thread that raises turns the daemon's exit code non-zero
    and is named in a THREAD_FAILED= line, instead of dying silently."""
    from repro.core.dist_ingest import DistBatchWriter
    from repro.serve_db.__main__ import main

    def broken_add(self, *a, **kw):
        raise RuntimeError("injected writer fault")

    monkeypatch.setattr(DistBatchWriter, "add", broken_add)
    try:
        rc = main(
            [
                "--rows", "1200", "--sessions", "1", "--writers", "1",
                "--duration", "1.0", "--incident-dir", str(tmp_path / "inc"),
                "--groups", "1", "--tablets-per-device", "2",
            ]
        )
    finally:
        obs.flight_disable()
        obs.flight_clear()
    assert rc == 1
    assert "THREAD_FAILED=writer-0" in capsys.readouterr().out
