"""The chip benchmark's package on the path, and its cells cut to a size
the CPU runs in seconds (the same code paths, tiny tables)."""
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks", "chip")
sys.path.insert(0, os.path.abspath(BENCH))

TINY_CONFIG = dict(tablets_per_device=2, capacity=1 << 12, mem_rows=128, max_runs=2,
                   append_rows=128, fill_limit_rows=6000, n_domains=200)


def tiny(name: str):
    """The named cell, resolved from BENCHMARK.json, at a CPU test size."""
    from chipbench import harness

    cell = harness.resolve(name)
    cell.config.update(TINY_CONFIG)
    tr = cell.traffic
    if "ingest" in tr:
        tr["ingest"]["chunk_rows"] = 256
    if "fill" in tr:
        tr["fill"]["chunk_rows"] = 1000
    if "queries" in tr:
        tr["queries"]["tiers"]["C"] = {"rows": [1, 10]}
        tr["queries"]["rate_per_s"] = 5.0
    return cell


@pytest.fixture
def tiny_cell():
    return tiny
