"""Ahead-of-time compiles of the device plane and query programs for a
described TPU v5e chip. No chip is needed: the TPU compiler runs here and
refuses what the chip's compiler would refuse (tiling, VMEM, HBM fit).

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports this file. Shapes are the one-chip smoke's
widths (8 tablets, all 12 web-proxy fields indexed, 4 run slots) with the
per-tablet capacity cut to 2^14 and memtables to 128 rows, and every
program compiles concurrently, so the file runs in well under a minute;
chip_smoke.py runs the same programs at 2^18 rows and 4096-row memtables
on the chip.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import AggregateSpec, Eq, EventStore, web_proxy_schema
from repro.core.dist_ingest import _PlanePrograms
from repro.core.dist_query import (
    build_aggregate_step,
    build_density_step,
    build_index_step,
    build_scan_step,
)
from repro.core.filter import compile_tree
from repro.core.iterators import resolve_grouping
from repro.kernels.filter_scan.ops import pad_program

CAPACITY = 1 << 14
TABLETS = 8
MEM_ROWS = 128
MAX_RUNS = 4
TOP_K = 128
T_STOP = 4 * 3600
HBM_BYTES = 16 * 10**9  # one v5e chip


def make_programs(mesh):
    schema = web_proxy_schema()
    return _PlanePrograms(
        mesh, schema.n_fields, CAPACITY, TABLETS, MEM_ROWS, MAX_RUNS,
        append_rows=1024, indexed_fids=tuple(range(schema.n_fields)),
        agg_bucket_s=3600, kernel_backend="auto",
    )


def _replicated(mesh, shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(*([None] * len(shape))))
    )


def plane_programs(pr):
    """(name, jitted step, abstract args) of every ingest-plane program:
    append, minor, fold_one, major and the seal."""
    b, f = pr.append_rows, pr.n_fields
    run_names, base_names = pr._major_names()
    folds = (pr.abstract_state(run_names), pr.abstract_state(base_names))
    out = [
        ("append", pr.append_step(), (
            pr.abstract_state(pr._append_names()),
            _replicated(pr.mesh, (b,), jnp.int32),
            _replicated(pr.mesh, (b, f), jnp.int32),
            _replicated(pr.mesh, (b,), jnp.int32),
        )),
        ("minor", pr.minor_step(), (pr.abstract_state(pr._minor_names()),)),
        ("fold_one", pr.fold_one_step(), folds),
        ("major", pr.major_step(), folds),
    ]
    # One seal bucket (the largest): every bucket is the same program at
    # another sort length, and each is a separate TPU sort compile.
    seal_args = (pr.abstract_state(pr._seal_names()),)
    return out + [("seal", pr.seal_step(pr.mem_rows), seal_args)]


def query_programs(pr):
    """(name, jitted step, abstract args) of the read programs a QueryService
    runs for Eq queries and a count-per-status-per-hour aggregate: the
    scan, index, density and aggregate steps over every LSM level."""
    mesh = pr.mesh
    store = EventStore(web_proxy_schema())
    store.dictionaries["domain"].encode("d00000.example.com")
    for s in ("200", "304", "404", "500", "302"):
        store.dictionaries["status"].encode(s)
    store.total_rows, store.ts_min, store.ts_max = 1, 0, T_STOP
    opc, a0, a1, cs = pad_program(compile_tree(store, Eq("domain", "d00000.example.com")))
    prog = tuple(_replicated(mesh, x.shape, jnp.int32) for x in (opc, a0, a1, cs))
    st = pr.abstract_state(pr.state_layout())
    ev = tuple(st[n] for n in ("ev_base_k", "ev_base_c", "ev_base_n"))
    ev_lv = tuple(st[n] for n in (
        "ev_run_k", "ev_run_c", "ev_run_n", "ev_mem_k", "ev_mem_c", "ev_mem_n"))
    ix_lv = tuple(st[n] for n in ("ix_run_k", "ix_run_n", "ix_mem_k", "ix_mem_n"))
    ag_lv = tuple(st[n] for n in (
        "ag_run_k", "ag_run_c", "ag_run_n", "ag_mem_k", "ag_mem_c", "ag_mem_n"))
    s32 = _replicated(mesh, (), jnp.int32)
    s64 = _replicated(mesh, (), jnp.int64)
    g = resolve_grouping(store, AggregateSpec(group_by=("status",), time_bucket_s=3600), 0, T_STOP)
    vt = _replicated(mesh, (1,), jnp.int32)
    return [
        ("scan", build_scan_step(mesh, pr.n_fields, len(opc), cs.shape, TOP_K, runs=True),
         ev + ev_lv + prog + (s32, s32)),
        ("index", build_index_step(mesh, 1, "intersect", len(opc), cs.shape, TOP_K, runs=True),
         (st["ev_base_k"], st["ev_base_c"], st["ix_base_k"]) + ev_lv + ix_lv + prog
         + (_replicated(mesh, (1,), jnp.int64),) * 2),
        ("density", build_density_step(mesh, runs=True),
         (st["ag_base_k"], st["ag_base_c"]) + ag_lv + (s64, s64)),
        ("aggregate", build_aggregate_step(
            mesh, g.fids, g.strides, g.size, g.n_buckets, g.spec.time_bucket_s,
            g.spec.op, g.value_fid, runs=True),
         ev + ev_lv + prog + (vt, s32, s32, s32)),
    ]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    """{program name: [future of its Compiled]} for one described chip —
    every lower + compile submitted at once (XLA compiles outside the
    GIL); each test reads its own programs' results, so a refusal fails
    the test of the program that was refused."""
    # Compiles for a described chip cannot be read back from a persistent
    # cache without the chip: keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mesh = Mesh(
        np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    pr = make_programs(mesh)
    progs = plane_programs(pr) + query_programs(pr)
    out = {}
    with ThreadPoolExecutor(len(progs)) as pool:
        for name, step, args in progs:
            out.setdefault(name, []).append(
                pool.submit(lambda s, a: s.lower(*a).compile(), step, args)
            )
        yield out
    jax.config.update("jax_enable_compilation_cache", prev)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return used


@pytest.mark.parametrize("which", ["append", "minor", "fold_one", "major", "seal"])
def test_plane_program_compiles_for_v5e(compiled, which):
    assert compiled[which]
    for fut in compiled[which]:
        _fits(fut.result())


@pytest.mark.parametrize("which", ["scan", "index", "density", "aggregate"])
def test_query_program_compiles_for_v5e(compiled, which):
    (fut,) = compiled[which]
    _fits(fut.result())
