"""The chip benchmark's one general harness.

A cell names a configuration (configs/<name>.json: the deployment's
sizes and guarantees) and a traffic mix (traffic/<name>.json: which
clients run and at what load). The harness reads both, builds the data
from the seed, warms every shape the cell uses, measures for the given
seconds, reads each metric through its own reader (metrics/<name>.py),
and checks every result against the numpy reference (reference.py).

A traffic file may hold any of these sections:

  "fill"     the table is filled to the configuration's fill limit through
             the plane's pre-encoded entry during set-up, then compacted
             and published;
  "ingest"   writer clients (DistBatchWriter) add string-valued batches
             during the window, closed loop, until the window closes or
             their share of the fill limit is written;
             afterwards every acknowledged row is read back through the
             query path;
  "queries"  analysts submit Eq(domain) queries over the whole time range
             to the serve plane, open loop, at a fixed rate; each is
             timed from when it was due.

What the program under test provides is imported from the checkout's
src/: the event store and its dictionaries, the ingest plane and its
writers, the query service, and its spans and counters.
"""
from __future__ import annotations

import importlib.util
import json
import math
import threading
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import reference as refmod
from . import trace as tracemod
from .generator import FIELDS, WebProxyGenerator

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]
T_STOP = 4 * 3600  # the paper's 4-hour query window
LIMITS_KEY = "checks"


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here."""


# ------------------------------------------------------------------ files
def load_benchmark(root: Path = CHECKOUT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def with_held_out(bench: Dict) -> Dict:
    """BENCHMARK.json's entries and, after them, held_out.json's: cells
    that run and are tested but are not yet part of the benchmark."""
    path = BENCH_DIR / "held_out.json"
    held = json.loads(path.read_text()) if path.exists() else {}
    return {k: bench[k] + held.get(k, []) if k in ("workloads", "end_to_end", "per_layer") else bench[k]
            for k in bench}


def resolve(name: str, bench: Optional[Dict] = None) -> Cell:
    """The cell, its configuration and traffic files, and its metrics."""
    bench = with_held_out(bench if bench is not None else load_benchmark())
    wl = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], wl["config"], "config")
    config = json.loads((CHECKOUT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{wl['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(wl["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def metric_reader(name: str) -> Callable:
    """metrics/<name>.py's read(artifacts) -> number or None."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ artifacts
@dataclass
class QueryRecord:
    due: float
    tier: str
    scheme: str
    code: int
    stream: object = None
    submitted: float = math.nan
    batches: Optional[list] = None
    error: Optional[str] = None
    done_by: float = math.nan  # when the drain gave up on it

    @property
    def ok(self) -> bool:
        return self.batches is not None and self.error is None

    def ttfr_s(self) -> float:
        """Due -> first result delivered; a query with none counts the wait
        until the drain gave up."""
        at = getattr(self.stream, "first_result_at", None)
        return (at if at is not None else self.done_by) - self.due

    def total_s(self) -> float:
        """Due -> last batch delivered (the stream finished)."""
        at = getattr(self.stream, "finished_at", None) if self.ok else None
        return (at if at is not None else self.done_by) - self.due


@dataclass
class Artifacts:
    """What a run leaves for the metric readers."""

    cell: str
    traced: bool
    setup_s: float = math.nan
    window_s: float = math.nan  # elapsed, start to the last client's end
    writers: int = 0
    acked_rows: int = 0
    blocked_s: float = 0.0  # writers' blocked seconds in the window, summed
    spans: List[Dict] = field(default_factory=list)  # program spans in the window
    queries: List[QueryRecord] = field(default_factory=list)
    trace: Optional[Dict] = None  # trace.reduce() of the window


# ------------------------------------------------------------------ data
@dataclass
class Data:
    ts: np.ndarray
    codes: np.ndarray
    vocab: Dict[str, np.ndarray]  # numpy str arrays, index = code
    strings: Dict[str, np.ndarray]  # the same values as Python str objects


def make_data(config: Dict, seed: int) -> Data:
    gen = WebProxyGenerator(seed, n_domains=config["n_domains"], zipf_a=config["zipf_a"])
    vocab = gen.vocabulary()
    ts, codes = gen.gen_codes(int(config["fill_limit_rows"]), 0, T_STOP - 1)
    strings = {f: vocab[f].astype(object) for f in FIELDS}
    return Data(ts, codes, vocab, strings)


def tier_domains(codes: np.ndarray, tiers: Dict, field_id: int, n_domains: int) -> Dict[str, np.ndarray]:
    """Domain codes in each tier's band of row counts. A band is
    [lo, hi] rows, each bound either absolute ("rows") or a share of the
    most popular domain's rows ("top_share")."""
    counts = np.bincount(codes[:, field_id], minlength=n_domains)
    top = counts.max()
    out = {}
    for tier, band in tiers.items():
        lo, hi = band["rows"] if "rows" in band else (x * top for x in band["top_share"])
        members = np.flatnonzero((counts >= lo) & (counts <= hi))
        if members.size == 0:
            raise BenchError(f"tier {tier}: no domain has {lo}-{hi} rows")
        out[tier] = members
    return out


# ------------------------------------------------------------ the system
class System:
    """The program under test, built for one configuration."""

    def __init__(self, config: Dict, mesh, data: Data):
        from repro.core import EventStore, web_proxy_schema
        from repro.core.dist_ingest import DistIngestPlane

        self.config = config
        self.schema = web_proxy_schema()
        if tuple(self.schema.field_names()) != FIELDS:
            raise BenchError("schema and generator field orders differ")
        self.store = EventStore(self.schema, n_shards=8)
        # Every value pre-encoded in vocabulary order: a value's dictionary
        # code is its index in the generated arrays.
        for f in FIELDS:
            got = self.store.dictionaries[f].encode_many(data.strings[f])
            if not np.array_equal(got, np.arange(len(got))):
                raise BenchError(f"{f}: dictionary codes not in vocabulary order")
        self.plane = DistIngestPlane.for_store(
            self.store, mesh,
            capacity=int(config["capacity"]),
            tablets_per_device=int(config["tablets_per_device"]),
            mem_rows=int(config["mem_rows"]),
            max_runs=int(config["max_runs"]),
            append_rows=int(config["append_rows"]),
            n_groups=int(config["n_groups"]),
        )
        self.mesh = mesh
        self.n_tablets = self.plane.n_tablets
        self.field_ids = {f: i for i, f in enumerate(FIELDS)}

    def warm_plane(self) -> None:
        self.plane.precompile()
        self.plane.warm_compaction()
        self.plane.warm_seal()

    def service(self):
        from repro.serve_db import QueryService

        return QueryService(self.store, self.plane, top_k=int(self.config["top_k"]))

    def eq_domain(self, vocab, code: int):
        from repro.core import Eq

        return Eq("domain", str(vocab["domain"][code]))

    def overflow(self) -> int:
        tele = self.plane.telemetry()
        return int(tele["overflow"].sum()) + sum(
            int(tele[f"{f.name}_overflow"].sum()) for f in self.plane.families[1:]
        )

    def peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.mesh.devices.flat]
        return int(max(peaks))


# --------------------------------------------------------------- clients
class Writers:
    """W DistBatchWriter clients over disjoint, interleaved shares of the
    generated rows (row i belongs to writer i % W, so writers overlap in
    time). The first `warm_rows` of each share are written during set-up,
    which compiles the append path."""

    def __init__(self, sysm: System, data: Data, spec: Dict):
        from repro.core.dist_ingest import DistBatchWriter

        self.sys, self.data, self.spec = sysm, data, spec
        self.n = int(spec["writers"])
        self.chunk = int(spec["chunk_rows"])
        n_rows = len(data.ts)
        self.share = [np.arange(w, n_rows, self.n) for w in range(self.n)]
        self.writers = [
            DistBatchWriter(sysm.store, sysm.plane, batch_rows=self.chunk, writer_id=w)
            for w in range(self.n)
        ]
        self.acked = [0] * self.n  # rows of each share acknowledged
        self.warm_rows = 0
        self.attempted = 0
        self.failed = 0
        self.errors: List[str] = []
        self.end = [math.nan] * self.n
        self._lock = threading.Lock()

    def _add(self, w: int, idx: np.ndarray) -> None:
        d = self.data
        vals = {f: d.strings[f][d.codes[idx, j]].tolist() for j, f in enumerate(FIELDS)}
        self.writers[w].add(d.ts[idx], vals)  # flushes: chunk == batch_rows

    def warm(self) -> None:
        """One chunk from writer 0, acknowledged like any other."""
        idx = self.share[0][: self.chunk]
        self._add(0, idx)
        self.acked[0] = self.warm_rows = idx.size

    def run(self, w: int, stop: threading.Event) -> None:
        import jax

        mine = self.share[w]
        off = self.acked[w]
        try:
            while off < mine.size and not stop.is_set():
                idx = mine[off: off + self.chunk]
                with self._lock:
                    self.attempted += 1
                with jax.profiler.TraceAnnotation("bench.add"):
                    self._add(w, idx)
                off += idx.size
                self.acked[w] = off
            with jax.profiler.TraceAnnotation("bench.flush"):
                self.writers[w].close()
        except Exception:
            with self._lock:
                self.failed += 1
                self.errors.append(traceback.format_exc())
        self.end[w] = time.perf_counter()

    def tablets(self) -> Tuple[np.ndarray, np.ndarray]:
        """(row indices, tablet) of every acknowledged row, replaying each
        writer's placement: its rows in the order it wrote them, numbered
        from 0."""
        d = self.data
        idx, tab = [], []
        for w, (s, a) in enumerate(zip(self.share, self.acked)):
            r = s[:a]
            idx.append(r)
            tab.append(refmod.writer_tablets(
                d.ts[r], d.codes[r], np.arange(a, dtype=np.int64),
                np.int64(w), self.sys.n_tablets))
        idx, tab = np.concatenate(idx), np.concatenate(tab)
        order = np.argsort(idx, kind="stable")
        return idx[order], tab[order]


class Analysts:
    """Open-loop Eq(domain) queries over S sessions. The plan is fixed
    before the window: N = rate x seconds queries whose gaps are the N
    quantiles of an exponential law at the rate (so every seed gets the
    same set of gaps) in an order drawn from the seed, each with a tier
    and scheme from a balanced list (in thirds by tier, evenly over the
    schemes) in seeded order, and a domain drawn from its tier's band."""

    def __init__(self, sysm: System, data: Data, spec: Dict, seed: int, seconds: float):
        self.sys, self.data, self.spec = sysm, data, spec
        rng = np.random.default_rng([seed, 0x51])
        self.bands = tier_domains(data.codes, spec["tiers"], sysm.field_ids["domain"],
                                  len(data.vocab["domain"]))
        rate = float(spec["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        due = np.cumsum(rng.permutation(gaps))
        combos = [(t, s) for t in sorted(self.bands) for s in spec["schemes"]]
        picks = rng.permutation(np.arange(n) % len(combos))
        self.plan = []
        for d, k in zip(due, picks):
            tier, scheme = combos[k]
            code = int(rng.choice(self.bands[tier]))
            self.plan.append(QueryRecord(due=float(d), tier=tier, scheme=scheme, code=code))
        self.svc = None
        self.sessions = []
        self.lateness: List[float] = []

    def start_service(self) -> None:
        self.svc = self.sys.service()
        self.sessions = [self.svc.session(f"analyst-{i}") for i in range(int(self.spec["sessions"]))]

    def warm(self, ref: refmod.Reference) -> int:
        """One query per tier and scheme, checked: every read program the
        window runs is compiled before it. Returns the wrong answers."""
        wrong = 0
        s = self.svc.session("warm")
        for tier in sorted(self.bands):
            code = int(self.bands[tier][len(self.bands[tier]) // 2])
            for scheme in self.spec["schemes"]:
                batches = s.submit(scheme, 0, T_STOP, self.sys.eq_domain(self.data.vocab, code)).drain(
                    timeout=float(self.spec["drain_s"]))
                wrong += query_wrong(ref, code, batches)
        s.close()
        return wrong

    def run(self, t0: float, stop: threading.Event) -> None:
        import jax

        for i, q in enumerate(self.plan):
            q.due += t0
            delay = q.due - time.perf_counter()
            if (delay > 0 and stop.wait(delay)) or stop.is_set():
                del self.plan[i:]  # not due inside the window: never sent
                break
            with jax.profiler.TraceAnnotation("bench.submit"):
                q.submitted = time.perf_counter()
                q.stream = self.sessions[i % len(self.sessions)].submit(
                    q.scheme, 0, T_STOP, self.sys.eq_domain(self.data.vocab, q.code))
            self.lateness.append(q.submitted - q.due)

    def drain(self, deadline: float) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.drain"):
            for q in self.plan:
                try:
                    q.batches = q.stream.drain(timeout=max(0.001, deadline - time.perf_counter()))
                except Exception as e:  # timed out (queue.Empty) or the query failed
                    q.error = f"{type(e).__name__}: {e}"
                    q.done_by = time.perf_counter()

    def replan(self, rate: float, seed: int, seconds: float) -> "Analysts":
        """A new plan at another rate, on the same service and sessions."""
        an = Analysts(self.sys, self.data, dict(self.spec, rate_per_s=rate), seed, seconds)
        an.svc, an.sessions = self.svc, self.sessions
        return an

    def notes(self) -> Dict:
        out: Dict = {"query_errors": [q.error for q in self.plan if q.error][:2]}
        if self.lateness:
            out["generator_late_s"] = {"max": max(self.lateness),
                                       "p95": float(np.quantile(self.lateness, 0.95))}
        return out

    def checks(self, ref: refmod.Reference) -> Dict[str, Dict]:
        """Every query due in the window answered, and every batch exact."""
        return {
            "queries_wrong": {"value": sum(query_wrong(ref, q.code, q.batches)
                                           for q in self.plan if q.ok), "limit": 0},
            "queries_unanswered": {"value": sum(not q.ok for q in self.plan), "limit": 0},
        }

    def close(self) -> None:
        if self.svc is not None:
            for s in self.sessions:
                s.close()
            self.svc.close()
            self.svc = None


# ------------------------------------------------------------------- run
class Compiles:
    """Counts lowerings and backend compiles while `on` is set."""

    EVENTS = {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
        "/jax/core/compile/backend_compile_duration": "compiled",
    }

    def __init__(self):
        import jax.monitoring

        self.on = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._note)

    def _note(self, event: str, duration: float, **kw) -> None:
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._note)


def _threads(targets: List[Tuple[str, Callable[[], None]]]) -> List[threading.Thread]:
    ts = [threading.Thread(target=f, name=n, daemon=True) for n, f in targets]
    for t in ts:
        t.start()
    return ts


@dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, Dict]
    device: Dict
    checks: Dict[str, Dict]
    breakdown: Optional[Dict]
    notes: Dict


@dataclass
class Prepared:
    """A cell set up and ready for its window: the data, the program with
    its table, the reference over what set-up wrote, and the clients."""

    data: Data
    sysm: System
    ref: Optional[refmod.Reference]
    writers: Optional[Writers]
    analysts: Optional[Analysts]
    checks: Dict[str, Dict]


def prepare(cell: Cell, seed: int, seconds: float, mesh,
            log: Callable[[str], None] = print) -> Prepared:
    """Set-up: data from the seed, the plane built and warmed, the table
    filled where the traffic asks for it, and every client warmed."""
    cfg, tr = cell.config, cell.traffic
    t = time.perf_counter()
    data = make_data(cfg, seed)
    log(f"phase generate: {time.perf_counter() - t}s, {len(data.ts)} events")
    t = time.perf_counter()
    sysm = System(cfg, mesh, data)
    sysm.warm_plane()
    log(f"phase plane build+warm: {time.perf_counter() - t}s")
    ref = None  # the reference over the rows the table holds
    if "fill" in tr:
        t = time.perf_counter()
        ref = _reference(sysm, data, np.arange(len(data.ts)),
                         fill(sysm, data, int(tr["fill"]["chunk_rows"])))
        if tr["fill"].get("compact", False):
            sysm.plane.compact("explicit")
        sysm.plane.publish()
        log(f"phase fill: {time.perf_counter() - t}s")
    writers = None
    if "ingest" in tr:
        if "fill" in tr:
            raise BenchError("a traffic mix with both fill and ingest would write rows twice")
        writers = Writers(sysm, data, tr["ingest"])
        t = time.perf_counter()
        writers.warm()
        log(f"phase writer warm: {time.perf_counter() - t}s")
    analysts = None
    checks: Dict[str, Dict] = {}
    if "queries" in tr:
        if ref is None:
            raise BenchError("queries need a filled table")
        t = time.perf_counter()
        analysts = Analysts(sysm, data, tr["queries"], seed, seconds)
        analysts.start_service()
        checks["warm_queries_wrong"] = {"value": analysts.warm(ref), "limit": 0}
        log(f"phase query warm: {time.perf_counter() - t}s, {len(analysts.plan)} queries planned")
    return Prepared(data, sysm, ref, writers, analysts, checks)


def run_window(prep: Prepared, seconds: float) -> Tuple[float, float]:
    """The measured window: every client at once, until the deadline or
    until the writers ran out of rows. Returns its start and end."""
    writers, analysts = prep.writers, prep.analysts
    stop = threading.Event()
    t0 = time.perf_counter()
    threads = []
    if writers is not None:
        threads += _threads([(f"writer-{w}", lambda w=w: writers.run(w, stop))
                             for w in range(writers.n)])
    if analysts is not None:
        threads += _threads([("analysts", lambda: analysts.run(t0, stop))])
    deadline = t0 + seconds
    while any(th.is_alive() for th in threads) and time.perf_counter() < deadline:
        time.sleep(0.005)
    stop.set()
    for th in threads:
        th.join()
    # The window closes at the deadline, or with the last writer: after it
    # when a writer finishes its batch in hand, before it when the writers
    # ran out of rows (the fill limit) and no analyst is due.
    ends = [deadline] if analysts is not None else []
    if writers is not None:
        ends.append(max(writers.end))
    return t0, max(ends)


def execute(cell: Cell, seed: int, seconds: float, traced: bool, mesh, t_process: float,
            log: Callable[[str], None] = print, work_dir: Optional[Path] = None) -> Outcome:
    """One run of a cell: set-up, window, metrics, check."""
    import jax

    from repro.obs import trace as obs_trace

    tr = cell.traffic
    art = Artifacts(cell=cell.name, traced=traced)
    notes: Dict = {}
    compiles = Compiles()
    prep = prepare(cell, seed, seconds, mesh, log)
    sysm, writers, analysts = prep.sysm, prep.writers, prep.analysts
    checks = dict(prep.checks)
    blocked0 = float(sysm.plane.blocked_seconds)

    # ---- window
    trace_dir = None
    if traced:
        trace_dir = (work_dir or CHECKOUT / ".bench_work") / "trace"
        _clear_dir(trace_dir)
        tracemod.start(str(trace_dir))
        obs_trace.enable()
        obs_trace.clear()
    compiles.on = True
    with jax.profiler.TraceAnnotation(tracemod.WINDOW):
        t0, t_end = run_window(prep, seconds)
    compiles.on = False
    compiles.close()
    art.setup_s = t0 - t_process
    art.window_s = t_end - t0
    if analysts is not None:
        analysts.drain(t_end + float(tr["queries"]["drain_s"]))
    if traced:
        art.spans = [r for r in obs_trace.get_tracer().records if r["t0"] >= 0.0]
        obs_trace.disable()
        tracemod.stop()
    notes["window_s"] = art.window_s
    notes["compiles_in_window"] = dict(compiles.counts)
    device = {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": int(mesh.devices.size),
        "memory_peak_bytes": sysm.peak_bytes(),
    }

    # ---- artifacts for the readers
    attempted = failed = 0
    if writers is not None:
        art.writers = writers.n
        art.acked_rows = int(sum(writers.acked)) - writers.warm_rows  # the warm chunk was set-up
        art.blocked_s = float(sysm.plane.blocked_seconds) - blocked0
        attempted += writers.attempted
        failed += writers.failed
        notes["writer_errors"] = writers.errors[:2]
    if analysts is not None:
        art.queries = analysts.plan
        attempted += len(analysts.plan)
        failed += sum(not q.ok for q in analysts.plan)
        notes.update(analysts.notes())
    if traced:
        t = time.perf_counter()
        red = tracemod.reduce(tracemod.load(str(trace_dir)))
        _clear_dir(trace_dir)
        art.trace = red
        log(f"phase trace read: {time.perf_counter() - t}s")
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]

    metric_list = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in metric_list:
        v = metric_reader(m["name"])(art)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- check against the reference, once the window is closed
    t = time.perf_counter()
    ref = prep.ref
    if writers is not None:
        ref = _reference(sysm, prep.data, *writers.tablets())
    checks.update(_check_table(sysm, ref))
    if analysts is not None:
        analysts.close()
        checks.update(analysts.checks(ref))
    if "readback" in tr:
        checks.update(_readback(sysm, prep.data, ref, tr["readback"]))
    log(f"phase check: {time.perf_counter() - t}s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return Outcome(correct, attempted, failed, metrics, device, checks,
                   art.trace["breakdown"] if traced and art.trace else None, notes)


def fill(sysm: System, data: Data, chunk: int) -> np.ndarray:
    """Every generated row through the plane's pre-encoded entry, placed as
    one writer (id 0) would place them. Returns each row's tablet."""
    import jax

    n = len(data.ts)
    tab = refmod.writer_tablets(data.ts, data.codes, np.arange(n, dtype=np.int64),
                                np.int64(0), sysm.n_tablets)
    rts = (refmod.TS_MAX - data.ts).astype(np.int32)
    with jax.profiler.TraceAnnotation("bench.fill"):
        for off in range(0, n, chunk):
            sl = slice(off, off + chunk)
            sysm.plane.ingest(rts[sl], data.codes[sl], tab[sl].astype(np.int32))
    return tab


def _reference(sysm: System, data: Data, rows: np.ndarray, tabs: np.ndarray) -> refmod.Reference:
    order = np.argsort(rows, kind="stable")
    rows, tabs = rows[order], tabs[order]
    return refmod.Reference(data.ts[rows], data.codes[rows], tabs, sysm.n_tablets,
                            int(sysm.config["top_k"]), sysm.field_ids)


def query_wrong(ref: refmod.Reference, code: int, batches) -> bool:
    """Whether one Eq(domain) query's batches disagree with the reference."""
    return bool(ref.check_stream(ref.matching("domain", code), batches, 0, T_STOP)[1])


def _check_table(sysm: System, ref: refmod.Reference) -> Dict[str, Dict]:
    """Every acknowledged row is in its tablet, and nothing overflowed."""
    got = np.asarray(sysm.plane.telemetry()["rows"])
    return {
        "tablet_rows_off": {"value": int(np.count_nonzero(got != ref.tablet_rows())), "limit": 0},
        "overflow": {"value": sysm.overflow(), "limit": 0},
    }


def _readback(sysm: System, data: Data, ref: refmod.Reference, spec: Dict) -> Dict[str, Dict]:
    """Read the acknowledged rows back through the query path: Eq(domain)
    queries per tier and scheme, and a count per group and hour over the
    whole range, each against the reference."""
    from repro.core import AggregateSpec

    tiers = tier_domains(ref.codes, spec["tiers"], sysm.field_ids["domain"], len(data.vocab["domain"]))
    svc = sysm.service()
    wrong = 0
    cells_wrong = 0
    try:
        s = svc.session("readback")
        for tier in sorted(tiers):
            code = int(tiers[tier][0])
            for scheme in spec["schemes"]:
                batches = s.submit(scheme, 0, T_STOP, sysm.eq_domain(data.vocab, code)).drain(
                    timeout=float(spec["drain_s"]))
                wrong += query_wrong(ref, code, batches)
        for fld in spec.get("count_per", []):
            agg = AggregateSpec(group_by=(fld,), time_bucket_s=3600)
            (rb,) = s.submit_aggregate(agg, 0, T_STOP).drain(timeout=float(spec["drain_s"]))
            res = rb.blocks[0]
            codes_, bucket_ts = res.grouping.unpack(res.gids)
            got = {(int(c), int(b)): int(k) for c, b, k in zip(codes_[fld], bucket_ts, res.counts)}
            cells_wrong += refmod.aggregate_faults(ref.count_per(fld, 3600, 0, T_STOP), got)
        s.close()
    finally:
        svc.close()
    return {"readback_queries_wrong": {"value": wrong, "limit": 0},
            "readback_count_cells_wrong": {"value": cells_wrong, "limit": 0}}


def _clear_dir(path: Path) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True, exist_ok=True)


# ----------------------------------------------------------------- report
def result_line(out: Outcome, traced: bool) -> str:
    line = {
        "correct": out.correct,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": out.metrics,
        "device": out.device,
    }
    if traced and out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line[LIMITS_KEY] = out.checks
    return json.dumps(line)


def check_lines(out: Outcome) -> List[str]:
    return [f"check {k}: {v['value']} (limit {v['limit']})" for k, v in out.checks.items()]
