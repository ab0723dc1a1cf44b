"""Per-kernel validation: Pallas (interpret=True on CPU) and the jnp ref
vs pure-numpy oracles, with hypothesis sweeps over shapes/values."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import And, Eq, EventStore, Match, Not, Or, web_proxy_schema
from repro.core.filter import compile_tree, eval_tree_rows
from repro.kernels.aggregate_combine import combine_sorted_counts
from repro.kernels.filter_scan import filter_scan
from repro.kernels.merge_intersect import intersect_sorted, union_sorted


@pytest.fixture(scope="module")
def store():
    s = EventStore(web_proxy_schema(), n_shards=2)
    rng = np.random.default_rng(0)
    n = 4000
    vals = {
        "domain": rng.choice(["a.com", "ab.com", "b.com", "c.net"], size=n).tolist(),
        "method": rng.choice(["GET", "POST"], size=n).tolist(),
        "status": rng.choice(["200", "404", "500"], size=n).tolist(),
    }
    s.ingest(np.sort(rng.integers(0, 3600, n)), vals)
    return s


def _cols(store, rng, n):
    f = store.schema.n_fields
    cols = np.zeros((n, f), np.int32)
    for name in ["domain", "method", "status"]:
        fid = store.schema.field_id(name)
        cols[:, fid] = rng.integers(0, max(len(store.dictionaries[name]), 1), n)
    return cols


TREES = [
    Eq("domain", "a.com"),
    And(Eq("domain", "a.com"), Eq("method", "GET")),
    Or(Eq("domain", "b.com"), Eq("domain", "c.net"), Eq("domain", "a.com")),
    Not(Eq("status", "200")),
    And(Or(Eq("domain", "a.com"), Eq("domain", "ab.com")), Not(Eq("method", "POST")), Eq("status", "404")),
    Match("domain", "a"),
]


@pytest.mark.parametrize("tree", TREES)
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_filter_scan_vs_tree_oracle(store, tree, backend):
    rng = np.random.default_rng(7)
    cols = _cols(store, rng, 3000)
    prog = compile_tree(store, tree)
    got = filter_scan(cols, prog, backend=backend)
    want = eval_tree_rows(store, tree, cols)
    np.testing.assert_array_equal(got, want)


@given(n=st.integers(1, 5000), seed=st.integers(0, 2**31))
@settings(max_examples=15, deadline=None)
def test_filter_scan_shape_sweep(store, n, seed):
    rng = np.random.default_rng(seed)
    cols = _cols(store, rng, n)
    tree = And(Or(Eq("domain", "a.com"), Eq("domain", "b.com")), Not(Eq("status", "500")))
    prog = compile_tree(store, tree)
    for backend in ("ref", "pallas"):
        np.testing.assert_array_equal(
            filter_scan(cols, prog, backend=backend), eval_tree_rows(store, tree, cols)
        )


@given(
    na=st.integers(0, 3000),
    nb=st.integers(0, 3000),
    overlap=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**31),
)
@settings(max_examples=25, deadline=None)
def test_intersect_sweep(na, nb, overlap, seed):
    rng = np.random.default_rng(seed)
    a = np.unique(rng.integers(0, 1 << 52, na).astype(np.int64)) if na else np.empty(0, np.int64)
    take = int(min(len(a), nb) * overlap)
    extra = rng.integers(0, 1 << 52, max(nb - take, 0)).astype(np.int64)
    b = np.unique(np.concatenate([rng.choice(a, take, replace=False) if take else np.empty(0, np.int64), extra]))
    want = np.intersect1d(a, b)
    for backend in ("ref", "pallas"):
        got = intersect_sorted(a, b, backend=backend)
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(union_sorted(a, b), np.union1d(a, b))


def test_intersect_edge_keys():
    """Keys whose lo-lane bit patterns are negative int32 (the unsigned
    compare path)."""
    base = (1 << 32) - 2  # lo = 0xFFFFFFFE: negative as int32
    a = np.asarray([base - 1, base, base + 1, base + (1 << 33)], np.int64)
    b = np.asarray([base, base + (1 << 33)], np.int64)
    for backend in ("ref", "pallas"):
        np.testing.assert_array_equal(intersect_sorted(a, b, backend=backend), b)


@given(
    n=st.integers(1, 4000),
    nkeys=st.integers(1, 50),
    seed=st.integers(0, 2**31),
)
@settings(max_examples=25, deadline=None)
def test_combine_sweep(n, nkeys, seed):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.integers(0, nkeys, n).astype(np.int64))
    cnt = rng.integers(1, 10, n).astype(np.int32)
    uk, inv = np.unique(keys, return_inverse=True)
    want = np.bincount(inv, weights=cnt).astype(np.int32)
    for backend in ("ref", "pallas"):
        gk, gc = combine_sorted_counts(keys, cnt, backend=backend)
        np.testing.assert_array_equal(gk, uk)
        np.testing.assert_array_equal(gc, want)


def test_combine_boundary_straddling():
    """A single key spanning multiple Pallas tiles must merge across the
    tile-stitch epilogue."""
    from repro.kernels.aggregate_combine.aggregate_combine import BLOCK

    n = BLOCK * 3
    keys = np.full(n, 7, np.int64)
    cnt = np.ones(n, np.int32)
    gk, gc = combine_sorted_counts(keys, cnt, backend="pallas")
    assert list(gk) == [7]
    assert list(gc) == [n]


# ------------------------------------------------------------ backend policy
def test_auto_never_selects_a_pallas_kernel():
    """No Pallas kernel compiles for TPU yet, so `auto` is the reference
    on every platform; an unknown backend name is refused."""
    from repro.kernels.common import resolve_backend

    assert resolve_backend("auto") == "ref"
    assert resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError):
        resolve_backend("tpu")


@pytest.mark.parametrize("which", ["merge_sorted_device", "merge_sorted_runs", "intersect_sorted"])
def test_explicit_pallas_past_the_vmem_cap_raises(which):
    """An explicit pallas request a kernel cannot hold in VMEM raises
    instead of quietly switching to the reference."""
    import jax.numpy as jnp

    from repro.kernels.merge_intersect.ops import MAX_VMEM_KEYS as IX_CAP
    from repro.kernels.merge_runs import MAX_VMEM_KEYS, merge_sorted_device, merge_sorted_runs

    r = MAX_VMEM_KEYS // 2 + 1  # two runs pad past the cap
    with pytest.raises(ValueError, match="VMEM"):
        if which == "merge_sorted_device":
            merge_sorted_device(
                jnp.zeros((2, r), jnp.int32), jnp.zeros((2, r, 0), jnp.int32), backend="pallas"
            )
        elif which == "merge_sorted_runs":
            run = (np.arange(r, dtype=np.int64), np.zeros((r, 1), np.int32))
            merge_sorted_runs([run, run], backend="pallas")
        else:
            big = np.arange(IX_CAP + 1, dtype=np.int64)
            intersect_sorted(big, big, backend="pallas")
