"""Quickstart: the paper's pipeline in 60 seconds.

Builds a sharded event store, ingests synthetic web-proxy traffic, runs
the same query four ways (Scan / Batched Scan / Index / Batched Index —
paper §IV-B), then answers an aggregation with the server-side iterator
stack (fused filter+combine kernel) — per-group partials instead of rows.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import (
    AggregateSpec,
    And,
    Eq,
    EventStore,
    QueryProcessor,
    QueryStats,
    web_proxy_schema,
)
from repro.core.ingest import BatchWriter
from repro.pipeline.sources import SyntheticWebProxySource, parse_web_proxy_lines


def main():
    use_compile_cache()
    print("== build store (8 shards, as the paper's 8-node instance) ==")
    store = EventStore(web_proxy_schema(), n_shards=8)
    src = SyntheticWebProxySource(seed=1)
    writer = BatchWriter(store, batch_rows=8192)
    t0 = time.perf_counter()
    n = 60_000
    lines = src.gen_lines(n, 0, 4 * 3600)
    ts, cols = parse_web_proxy_lines(lines)
    writer.add(ts, cols, nbytes=sum(len(l) for l in lines))
    writer.close()
    store.flush_all()
    store.compact_all()
    dt = time.perf_counter() - t0
    print(f"ingested {n} events in {dt:.1f}s ({n/dt:,.0f} rows/s)\n")

    popular = src.domain_by_popularity(0.0)
    rare = src.domain_by_popularity(0.15)
    query = And(Eq("domain", popular), Eq("method", "GET"))
    print(f"query: domain={popular} AND method=GET over 4h of traffic")

    qp = QueryProcessor(store)
    for scheme in ["scan", "batched_scan", "index", "batched_index"]:
        stats = QueryStats()
        t0 = time.perf_counter()
        first = None
        rows = 0
        for blk in qp.run_scheme(scheme, 0, 4 * 3600, query, stats=stats):
            if first is None:
                first = time.perf_counter() - t0
            rows += blk.n
        total = time.perf_counter() - t0
        plan = stats.plan.describe() if stats.plan else "?"
        print(
            f"  {scheme:14s} first={1e3*(first or 0):8.2f} ms  total={1e3*total:8.2f} ms  "
            f"rows={rows}  batches={stats.batches}  plan={plan}"
        )

    print("\naggregation: count matching events per method per hour (iterator stack)")
    spec = AggregateSpec(group_by=("method",), op="count", time_bucket_s=3600)
    t0 = time.perf_counter()
    res = qp.aggregate(spec, 0, 4 * 3600, query)
    total = time.perf_counter() - t0
    shipped = res.gids.nbytes + res.values.nbytes + res.counts.nbytes
    print(
        f"  combine_scan   total={1e3*total:8.2f} ms  groups={res.n_groups}  "
        f"rows_combined={res.total_matched()}  client_bytes~{shipped}"
    )
    for row in sorted(res.rows(store), key=lambda r: r["bucket_ts"])[:4]:
        print(f"    {row['method']:5s} hour={row['bucket_ts']//3600}  count={row['value']}")


if __name__ == "__main__":
    main()
