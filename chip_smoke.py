#!/usr/bin/env python3
"""Chip smoke: the paper's pipeline end to end on a TPU, checked exactly.

    python3 chip_smoke.py [--seed N]      # one chip (the default)
    python3 chip_smoke.py --chips 4       # the four-chip mesh path only

One process drives the normal entry points: four DistBatchWriter threads
feed a DistIngestPlane (web-proxy schema, all 12 fields indexed) with
events generated from --seed over a 4-hour window; publish() snapshots
it; QueryService sessions run the paper's Query A/B/C under all four
schemes plus one aggregate_range (count per status per hour). Every
count, every delivered row and every aggregate row is compared with a
plain numpy evaluation of the generated arrays, and the plane must
report zero overflow and at least one major compaction.

Sizes (the model-configs "realistic size" for one v5e chip: a tablet
server's table, not a toy):
  one chip    8 tablets x capacity 2^18 rows, 4,096-row memtables,
              1,800,000 events
  four chips  4 x 2 tablets (the paper's 8 tablet servers) x capacity
              2^20 rows — the one-chip state on every chip — 4,096-row
              memtables, 7,200,000 events; also checks every chip's
              tablets got rows

Earlier lines report phase seconds, rows/s, compactions, time to first
result per tier, peak device memory, the compile-cache directory and the
kernel implementation each entry point selects. The last line is the
JSON contract {"ok": true, "device": {platform, kind, count}}. Without a
TPU, or without the repository's src/ next to this file, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
T_STOP = 4 * 3600  # the paper's 4-hour query window
SCHEMES = ("scan", "batched_scan", "index", "batched_index")
TOP_K = 128  # QueryService default: newest rows delivered per tablet per batch
WRITERS = 4  # DistBatchWriter threads, one client each
WRITER_CHUNK = 16384  # events a writer thread hands its DistBatchWriter at once
QUERY_TIMEOUT_S = 600.0


@dataclass(frozen=True)
class SmokeConfig:
    tablets_per_device: int
    capacity: int  # rows per tablet (base level)
    n_events: int
    mem_rows: int = 4096
    max_runs: int = 4


ONE_CHIP = SmokeConfig(tablets_per_device=8, capacity=1 << 18, n_events=1_800_000)
FOUR_CHIPS = SmokeConfig(tablets_per_device=2, capacity=1 << 20, n_events=7_200_000)


class SmokeFailure(AssertionError):
    """A result disagreed with the numpy reference, or a thread died."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- reference
def _fingerprints(ts: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """One uint64 per (ts, 12 codes) row, for multiset comparisons."""
    acc = ts.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    for j in range(codes.shape[1]):
        acc = (acc ^ codes[:, j].astype(np.uint64)) * np.uint64(0x100000001B3)
        acc ^= acc >> np.uint64(31)
    return acc


def _contains(big: np.ndarray, small: np.ndarray) -> bool:
    """Multiset containment small <= big."""
    bu, bc = np.unique(big, return_counts=True)
    su, sc = np.unique(small, return_counts=True)
    pos = np.searchsorted(bu, su)
    if np.any(pos >= bu.size):
        return False
    return bool(np.all(bu[pos] == su) and np.all(bc[pos] >= sc))


class Reference:
    """The plain numpy evaluation the device results must equal: Eq
    filters, inclusive time ranges, per-tablet newest-k delivery
    (BatchScanner semantics) and count-per-group aggregation over the
    generated arrays themselves."""

    def __init__(self, ts, codes, tablet, n_tablets, schema):
        self.ts = ts
        self.codes = codes
        self.tablet = tablet
        self.n_tablets = n_tablets
        self.schema = schema
        self.fp = _fingerprints(ts, codes)

    def matching(self, field: str, code: int) -> np.ndarray:
        """Indices of rows with field == code, in ts order (ts is sorted)."""
        return np.flatnonzero(self.codes[:, self.schema.field_id(field)] == code)

    def check_batch(self, rows: np.ndarray, rb) -> None:
        """One delivered ResultBatch against the rows matching its query:
        exact count; the delivered ts multiset equals each tablet's k
        newest; every delivered row is a real match; every row newer than
        its tablet's k-th newest was delivered."""
        lo, hi = int(rb.lo), int(rb.hi)
        ts_q = self.ts[rows]
        sel = rows[np.searchsorted(ts_q, lo, "left"):np.searchsorted(ts_q, hi, "right")]
        _check(rb.count == sel.size, f"batch [{lo},{hi}] count {rb.count} != {sel.size}")
        want_ts, sure = [], []
        tab = self.tablet[sel]
        for t in range(self.n_tablets):
            r_t = sel[tab == t]  # ascending ts
            top = r_t[-TOP_K:]
            want_ts.append(self.ts[top])
            if r_t.size > TOP_K:
                cut = self.ts[top[0]]
                sure.append(top[self.ts[top] > cut])
            else:
                sure.append(top)
        got_ts = np.asarray(rb.ts, np.int64)
        _check(
            np.array_equal(np.sort(got_ts), np.sort(np.concatenate(want_ts))),
            f"batch [{lo},{hi}] delivered ts multiset differs",
        )
        got_fp = _fingerprints(got_ts, np.asarray(rb.cols))
        _check(_contains(self.fp[sel], got_fp), f"batch [{lo},{hi}] delivered a non-matching row")
        _check(_contains(got_fp, self.fp[np.concatenate(sure)]),
               f"batch [{lo},{hi}] missed a row inside the newest-{TOP_K} cut")

    def check_stream(self, rows: np.ndarray, batches, t0: int, t1: int) -> Tuple[int, int]:
        """All batches of one query: each exact, and together they tile
        [t0, t1]. Returns (count, delivered rows)."""
        for rb in batches:
            self.check_batch(rows, rb)
        total = sum(rb.count for rb in batches)
        want = int(np.count_nonzero((self.ts[rows] >= t0) & (self.ts[rows] <= t1)))
        _check(total == want, f"query total {total} != {want}")
        return total, sum(len(rb.ts) for rb in batches)

    def count_per(self, field: str, bucket_s: int, t0: int, t1: int) -> Dict[Tuple[int, int], int]:
        """{(code, bucket start ts): rows} over [t0, t1]."""
        m = (self.ts >= t0) & (self.ts <= t1)
        codes = self.codes[m, self.schema.field_id(field)].astype(np.int64)
        buckets = self.ts[m] // bucket_s
        keys, counts = np.unique(codes * (1 << 32) + buckets, return_counts=True)
        return {(int(k >> 32), int(k & 0xFFFFFFFF) * bucket_s): int(c) for k, c in zip(keys, counts)}


# -------------------------------------------------------------------- phases
def _threads(targets: List[Tuple[str, Callable[[], None]]]) -> None:
    """Run each target on its own thread; raise if any of them raised."""
    errors: List[str] = []
    lock = threading.Lock()

    def guarded(name, fn):
        try:
            fn()
        except BaseException:
            with lock:
                errors.append(f"{name}:\n{traceback.format_exc()}")

    ts = [threading.Thread(target=guarded, args=(n, f), name=n) for n, f in targets]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    _check(not errors, "thread failed: " + "\n".join(errors))


def run(mesh, cfg: SmokeConfig, seed: int, log: Callable[[str], None] = print) -> Dict:
    """Generate, ingest, publish, query and check. Returns the report;
    raises SmokeFailure on any disagreement with the reference."""
    import jax

    from repro.core import AggregateSpec, Eq, EventStore, keypack, web_proxy_schema
    from repro.core.dist_ingest import DistBatchWriter, DistIngestPlane
    from repro.pipeline.sources import FIELDS, SyntheticWebProxySource
    from repro.serve_db import QueryService

    report: Dict = {}
    # ---- generate: codes from --seed, vocabularies pre-encoded in order so
    # a value's dictionary code is its index in the generated arrays.
    t = time.perf_counter()
    schema = web_proxy_schema()
    _check(schema.field_names() == FIELDS, "schema and generator field orders differ")
    src = SyntheticWebProxySource(seed=seed)
    vocab = src.vocabulary()
    ts, codes = src.gen_codes(cfg.n_events, 0, T_STOP - 1)
    store = EventStore(schema, n_shards=8)
    for f in FIELDS:
        got = store.dictionaries[f].encode_many(vocab[f].tolist())
        _check(np.array_equal(got, np.arange(len(vocab[f]))), f"{f}: codes not in vocabulary order")
    writer_of = np.arange(cfg.n_events) % WRITERS  # interleaved: writers overlap in time
    report["generate_s"] = time.perf_counter() - t
    log(f"phase generate: {report['generate_s']}s, {cfg.n_events} events")

    # ---- set up the plane and compile every ingest program before the clock
    t = time.perf_counter()
    plane = DistIngestPlane.for_store(
        store, mesh, capacity=cfg.capacity,
        tablets_per_device=cfg.tablets_per_device,
        mem_rows=cfg.mem_rows, max_runs=cfg.max_runs,
    )
    plane.precompile()  # every compaction + seal program, concurrently
    plane.warm_compaction()
    plane.warm_seal()
    report["plane_warm_s"] = time.perf_counter() - t
    log(f"phase plane compile/warm: {report['plane_warm_s']}s")

    # ---- ingest through four DistBatchWriter threads
    def writer(wid: int) -> Callable[[], None]:
        def body():
            w = DistBatchWriter(store, plane, writer_id=wid)
            mine = np.flatnonzero(writer_of == wid)
            for off in range(0, mine.size, WRITER_CHUNK):
                idx = mine[off:off + WRITER_CHUNK]
                w.add(ts[idx], {f: vocab[f][codes[idx, j]].tolist() for j, f in enumerate(FIELDS)})
            w.close()
        return body

    t = time.perf_counter()
    _threads([(f"writer-{w}", writer(w)) for w in range(WRITERS)])
    dist = plane.publish()
    jax.block_until_ready(dist.rev_ts if dist.groups is None else dist.groups[0].rev_ts)
    report["ingest_s"] = time.perf_counter() - t
    report["rows_per_s"] = cfg.n_events / report["ingest_s"]
    tele = plane.telemetry()
    report["minor"] = int(tele["minor"].sum())
    report["major"] = int(tele["major"].sum())
    report["overflow"] = int(tele["overflow"].sum()) + sum(
        int(tele[f"{f.name}_overflow"].sum()) for f in plane.families[1:]
    )
    report["writer_blocked_s"] = float(tele["blocked_seconds"])
    log(f"phase ingest: {report['ingest_s']}s, majors {report['major']}")

    # The writers' placement rule (row hash of the encoded event, the
    # writer's running row count and its id), replayed on the generated
    # arrays: which tablet each event must live in.
    nonce = np.zeros(cfg.n_events, np.int64)
    for w in range(WRITERS):
        m = writer_of == w
        nonce[m] = np.arange(int(m.sum()))
    h = keypack.short_hash(*(codes[:, j] for j in range(codes.shape[1])), ts, nonce, writer_of)
    tablet = (h % plane.n_tablets).astype(np.int64)
    ref = Reference(ts, codes, tablet, plane.n_tablets, schema)
    per_tablet = np.bincount(tablet, minlength=plane.n_tablets)
    _check(np.array_equal(tele["rows"], per_tablet), "per-tablet row counts differ from placement")
    _check(report["overflow"] == 0, f"overflow {report['overflow']}")
    _check(report["major"] >= 1, "ingest tripped no major compaction")
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    per_chip = {}
    for shard in plane.groups[0].state["rows"].addressable_shards:
        per_chip[shard.device.id] = int(np.asarray(shard.data).sum())
    report["rows_per_chip"] = per_chip
    _check(len(per_chip) == n_dev and min(per_chip.values()) > 0, f"a chip holds no rows: {per_chip}")

    # ---- Query A / B / C: most popular, somewhat popular, unpopular domain
    dom_counts = np.bincount(codes[:, schema.field_id("domain")], minlength=len(vocab["domain"]))
    ranked = np.argsort(-dom_counts, kind="stable")
    top = int(dom_counts[ranked[0]])
    tiers = {
        "A": int(ranked[0]),
        "B": int(next((d for d in ranked if dom_counts[d] <= 0.15 * top), ranked[len(ranked) // 4])),
        "C": int(next((d for d in ranked[::-1] if dom_counts[d] >= 30), ranked[-1])),
    }
    rows_of = {k: ref.matching("domain", c) for k, c in tiers.items()}
    report["tier_rows"] = {k: int(v.size) for k, v in rows_of.items()}
    spec = AggregateSpec(group_by=("status",), time_bucket_s=3600)
    want_agg = ref.count_per("status", 3600, 0, T_STOP)

    def tree(tier):
        return Eq("domain", str(vocab["domain"][tiers[tier]]))

    def check_agg(batches) -> int:
        (rb,) = batches
        res = rb.blocks[0]
        codes_, bucket_ts = res.grouping.unpack(res.gids)
        got = {
            (int(c), int(b)): int(n)
            for c, b, n in zip(codes_["status"], bucket_ts, res.counts)
        }
        _check(got == want_agg, "aggregate rows differ from the reference")
        _check(np.array_equal(res.values, res.counts), "count aggregate values != counts")
        return len(got)

    svc = QueryService(store, plane)
    try:
        # Warm pass: compile every read program these queries use (one
        # query shape per scheme + the aggregate), checked like the rest.
        t = time.perf_counter()
        s = svc.session("warm")
        for scheme in SCHEMES:
            batches = s.submit(scheme, 0, T_STOP, tree("C")).drain(timeout=QUERY_TIMEOUT_S)
            ref.check_stream(rows_of["C"], batches, 0, T_STOP)
        check_agg(s.submit_aggregate(spec, 0, T_STOP).drain(timeout=QUERY_TIMEOUT_S))
        s.close()
        report["query_warm_s"] = time.perf_counter() - t
        log(f"phase query compile/warm: {report['query_warm_s']}s")

        # Checked pass: one client session per scheme, concurrently.
        results: Dict[Tuple[str, str], Tuple] = {}
        lock = threading.Lock()

        def client(scheme: str) -> Callable[[], None]:
            def body():
                sess = svc.session(f"client-{scheme}")
                for tier in ("A", "B", "C"):
                    sq = sess.submit(scheme, 0, T_STOP, tree(tier))
                    batches = sq.drain(timeout=QUERY_TIMEOUT_S)
                    with lock:
                        results[(tier, scheme)] = (batches, sq.first_result_s, sq.total_s)
                sess.close()
            return body

        t = time.perf_counter()
        _threads([(f"client-{sc}", client(sc)) for sc in SCHEMES])
        s = svc.session("aggregate")
        agg_sq = s.submit_aggregate(spec, 0, T_STOP)
        agg_batches = agg_sq.drain(timeout=QUERY_TIMEOUT_S)
        s.close()
        report["query_s"] = time.perf_counter() - t
    finally:
        svc.close()

    # ---- compare with the reference (outside the timed window)
    report["queries"] = {}
    for (tier, scheme), (batches, ttfr, total_s) in sorted(results.items()):
        count, delivered = ref.check_stream(rows_of[tier], batches, 0, T_STOP)
        report["queries"][f"{tier}/{scheme}"] = {
            "count": count, "batches": len(batches), "delivered": delivered,
            "ttfr_s": ttfr, "total_s": total_s,
        }
    _check(len(results) == 3 * len(SCHEMES), "a query result is missing")
    report["aggregate_rows"] = check_agg(agg_batches)
    report["aggregate_ttfr_s"] = agg_sq.first_result_s
    report["ttfr_s"] = {
        tier: {sc: report["queries"][f"{tier}/{sc}"]["ttfr_s"] for sc in SCHEMES}
        for tier in ("A", "B", "C")
    }
    stats = jax.devices()[0].memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return report


def kernel_selection(plane_backend: str) -> Dict[str, str]:
    """Implementation each kernel entry point resolves to in this run."""
    from repro.kernels.common import resolve_backend

    entry = {
        "merge_runs (plane folds)": plane_backend,
        "filter_scan": "auto",
        "combine_scan": "auto",
        "aggregate_combine": "auto",
        "merge_intersect": "auto",
    }
    return {k: resolve_backend(v) for k, v in entry.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="data generator seed")
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="1: one-chip pipeline (default); 4: the four-chip mesh path only",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import use_compile_cache
        from repro.launch.mesh import make_dev_mesh
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not next to this file ({e})",
              file=sys.stderr)
        return 2

    cache_dir = use_compile_cache()
    cfg = ONE_CHIP if args.chips == 1 else FOUR_CHIPS
    mesh = make_dev_mesh(args.chips, 1)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": args.chips}
    print(f"device: {dev} mesh={dict(mesh.shape)} config={cfg}", flush=True)
    print(f"compile_cache_dir: {cache_dir}", flush=True)
    print(f"kernels: {kernel_selection('auto')}", flush=True)
    try:
        report = run(mesh, cfg, args.seed, log=lambda m: print(m, flush=True))
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    for key in ("generate_s", "plane_warm_s", "ingest_s", "rows_per_s", "query_warm_s",
                "query_s", "minor", "major", "overflow", "writer_blocked_s",
                "rows_per_chip", "tier_rows", "ttfr_s", "aggregate_rows",
                "aggregate_ttfr_s", "peak_bytes_in_use"):
        print(f"{key}: {report[key]}", flush=True)
    for name, q in report["queries"].items():
        print(f"query {name}: {q}", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
