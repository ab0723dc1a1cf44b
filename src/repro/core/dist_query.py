"""Distributed query execution — the paper's tablet-server scan on the
production TPU mesh.

The host-side EventStore (store.py) is the single-node reference; this
module is the scale-out data plane: every device of the (data, model) mesh
acts as one tablet server holding a fixed-capacity sorted columnar tablet,
and a query executes as ONE jitted shard_map program:

    time-range restriction   sorted rev_ts -> per-tablet searchsorted
    filter                   the same postfix predicate program the
                             Pallas filter_scan kernel executes
    project + count          local; global count via psum
    top-k newest             local top-k, then a gathered cross-tablet
                             merge on the host (BatchScanner semantics:
                             unordered across tablets)
    iterator-stack combine   the server-side CombinerIterator lowered into
                             the shard_map program: per-tablet fused
                             filter + dense segment aggregation, merged
                             across tablets with psum/pmin/pmax (the
                             group-id space is dense by construction —
                             see core/iterators.py ResolvedGrouping)

RUN-AWARE READS: every read primitive searches ALL LSM LEVELS of a
published DistIngestPlane snapshot — the major-compacted base, the K
sorted-run slabs from minor compactions, and a sealed (sorted) copy of
the memtable — for all three table families. Each level is sorted, so
the same searchsorted/filter/top-k machinery applies per level and the
per-tablet partials merge device-side (scan: rev_ts-ordered top-k merge
across levels; index: postings from every level feed the
intersect/union; aggregate/density: sums across levels, duplicates only
ever fold at major compaction). This is what lets
DistIngestPlane.publish() be a metadata flip instead of an O(capacity)
re-merge: freshness costs O(delta), not O(database), per the
high-rate-ingest literature (arXiv:1406.4923).

The adaptive batcher (Algs 1-2) drives this exactly like the host path:
each batch is one device-program invocation over a time sub-range — the
paper's design, 256 tablets wide. dryrun.py lowers + compiles it on the
single-pod and multi-pod meshes as the extra `llcysa-store` cells.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from . import keypack
from .batching import AdaptiveBatcher
from .filter import FilterProgram, compile_tree
from .iterators import AggregateResult, AggregateSpec, ResolvedGrouping, resolve_grouping
from .planner import QueryPlan, plan_query
from .store import EventStore
from ..obs import OwnedLock, span

INVALID_TS = jnp.int32(-1)
_I32_MAX = np.iinfo(np.int32).max


@dataclass
class DistStore:
    """Device-resident tablet grid — the paper's three tables per source,
    snapshotted at ALL LSM levels (base + sorted runs + sealed memtable).

    Event family (always present):

    rev_ts:  (T, R) int32   base run: reversed timestamps, ascending per
                            tablet (newest first), sentinel-padded
    cols:    (T, R, F) int32 dictionary codes, pad rows carry junk codes
                            (masked by counts in every scan)
    counts:  (T,) int32     live rows in the BASE level per tablet
    run_rev_ts: (T, K, M) int32  minor-compaction sorted-run slabs
    run_cols:   (T, K, M, F) int32
    run_counts: (T, K) int32     live rows per run slot (0 = empty/stale)
    mem_rev_ts: (T, M) int32     sealed memtable: sorted snapshot taken at
    mem_cols:   (T, M, F) int32  publish() time (the only per-publish
    mem_counts: (T,) int32       device work — O(memtable), not O(base))

    T = number of tablets = n_devices * tablets_per_device (T must divide
    evenly across the mesh); R = tablet capacity. The grid is either a
    bulk replay of a host store (from_event_store) or a live snapshot of
    a DistIngestPlane (dist_ingest.publish) — the latter updates
    incrementally as writers ingest, no re-scatter and NO fold: rows
    may live at any level and every read searches them all.

    Planes that maintain the index/aggregate families additionally expose
    the same three levels per family:

    ix_keys:  (T, Ci) int64  sorted packed index keys (field|value|rev_ts)
                             — postings for one (field, value) over a time
                             range are one contiguous slice, INT64_MAX pad
    ix_counts: (T,) int32    live postings in the base per tablet
    ix_run_k / ix_run_n, ix_mem_k / ix_mem_n — run + sealed levels
    ag_keys:  (T, Ca) int64  sorted packed aggregate keys
                             (field|value|bucket), unique per tablet AT
                             THE BASE level only (duplicates fold at
                             major); run/mem levels may repeat keys and
                             readers sum across levels
    ag_vals:  (T, Ca, 1) int64 occurrence counts per aggregate key
    ag_counts: (T,) int32    live aggregate keys in the base per tablet
    ag_run_k / ag_run_c / ag_run_n, ag_mem_k / ag_mem_c / ag_mem_n
    agg_bucket_s: int        the bucketing the densities were counted at

    Index/aggregate fields are None for index-less stores (a plane built
    without indexed_fids); DistQueryProcessor then falls back to
    filter-scan. Run/mem fields are None for base-only grids (a
    from_event_store bulk replay — folded up front, nothing unfolded to
    search — hand-built stores, dry-run shapes); reads then search the
    base alone.

    COMPOSITE snapshots: a sharded DistIngestPlane (n_groups > 1)
    publishes one DistStore whose ``groups`` tuple holds the per-group
    sub-snapshots in GLOBAL tablet order (group g owns the contiguous
    range [g * T/G, (g+1) * T/G)); the level arrays here are then None
    and every read primitive fans out over the sub-stores, summing
    counts and concatenating top-k slates host-side. Each sub-store
    keeps its OWN density_cache, so the planner's memoized densities for
    an untouched group survive publishes that only re-seal busy groups
    (sub-snapshots alias across publishes when a group is clean).
    ``gens`` maps "g<i>" to that group's level-generation dict.
    """

    rev_ts: Optional[jax.Array] = None
    cols: Optional[jax.Array] = None
    counts: Optional[jax.Array] = None
    mesh: Optional[Mesh] = None
    run_rev_ts: Optional[jax.Array] = None
    run_cols: Optional[jax.Array] = None
    run_counts: Optional[jax.Array] = None
    mem_rev_ts: Optional[jax.Array] = None
    mem_cols: Optional[jax.Array] = None
    mem_counts: Optional[jax.Array] = None
    ix_keys: Optional[jax.Array] = None
    ix_counts: Optional[jax.Array] = None
    ix_run_k: Optional[jax.Array] = None
    ix_run_n: Optional[jax.Array] = None
    ix_mem_k: Optional[jax.Array] = None
    ix_mem_n: Optional[jax.Array] = None
    ag_keys: Optional[jax.Array] = None
    ag_vals: Optional[jax.Array] = None
    ag_counts: Optional[jax.Array] = None
    ag_run_k: Optional[jax.Array] = None
    ag_run_c: Optional[jax.Array] = None
    ag_run_n: Optional[jax.Array] = None
    ag_mem_k: Optional[jax.Array] = None
    ag_mem_c: Optional[jax.Array] = None
    ag_mem_n: Optional[jax.Array] = None
    agg_bucket_s: Optional[int] = None
    # Level-generation tags at publish time ({"mem","runs","base"}):
    # which LSM levels this snapshot's buffers came from. Two snapshots
    # sharing a generation for a level ALIAS that level's arrays (the
    # plane's publish reuses untouched buffers across compact_step
    # increments instead of re-copying) — tests assert the identity.
    # None for hand-built / base-only stores. Composite snapshots nest
    # per-group dicts under "g<i>" keys instead.
    gens: Optional[Dict[str, object]] = None
    # Per-group sub-snapshots of a sharded plane publish (None for a
    # single-group or hand-built store): global tablet order, each a
    # complete single-group DistStore that reads recurse into.
    groups: Optional[Tuple["DistStore", ...]] = None
    # Per-snapshot memo for planner density reads (_agg_count_on): a
    # published snapshot is immutable, so a density within it never goes
    # stale; the memo dies with the snapshot at the next publish flip.
    density_cache: Dict[Tuple, int] = field(default_factory=dict, repr=False)

    @property
    def is_composite(self) -> bool:
        return self.groups is not None

    @property
    def n_tablets(self) -> int:
        if self.groups is not None:
            return sum(g.n_tablets for g in self.groups)
        return self.rev_ts.shape[0]

    @property
    def capacity(self) -> int:
        if self.groups is not None:
            return self.groups[0].capacity
        return self.rev_ts.shape[1]

    @property
    def has_index(self) -> bool:
        if self.groups is not None:
            return self.groups[0].has_index
        return self.ix_keys is not None

    @property
    def has_runs(self) -> bool:
        """True when the snapshot carries run + sealed-memtable levels
        (a plane publish); False for base-only grids."""
        if self.groups is not None:
            return self.groups[0].has_runs
        return self.run_rev_ts is not None


def tablet_specs(mesh: Mesh) -> Dict[str, P]:
    """Tablets shard over ALL mesh axes (every chip is a tablet server)."""
    axes = tuple(mesh.axis_names)
    return {
        "rev_ts": P(axes, None),
        "cols": P(axes, None, None),
        "counts": P(axes),
    }


def _ev_level_specs(axes) -> Tuple[P, ...]:
    """Partition specs for the event family's run + sealed-mem levels:
    (run_rev_ts, run_cols, run_counts, mem_rev_ts, mem_cols, mem_counts)."""
    return (
        P(axes, None, None), P(axes, None, None, None), P(axes, None),
        P(axes, None), P(axes, None, None), P(axes),
    )


def _ix_level_specs(axes) -> Tuple[P, ...]:
    """(ix_run_k, ix_run_n, ix_mem_k, ix_mem_n)."""
    return (P(axes, None, None), P(axes, None), P(axes, None), P(axes))


def _ag_level_specs(axes) -> Tuple[P, ...]:
    """(ag_run_k, ag_run_c, ag_run_n, ag_mem_k, ag_mem_c, ag_mem_n)."""
    return (
        P(axes, None, None), P(axes, None, None, None), P(axes, None),
        P(axes, None), P(axes, None, None), P(axes),
    )


def dist_store_shapes(mesh: Mesh, rows_per_tablet: int, n_fields: int, tablets_per_device: int = 1):
    """Abstract ShapeDtypeStructs for the dry-run (no allocation)."""
    t = int(np.prod([mesh.shape[a] for a in mesh.axis_names])) * tablets_per_device
    return {
        "rev_ts": jax.ShapeDtypeStruct((t, rows_per_tablet), jnp.int32),
        "cols": jax.ShapeDtypeStruct((t, rows_per_tablet, n_fields), jnp.int32),
        "counts": jax.ShapeDtypeStruct((t,), jnp.int32),
    }


def from_event_store(
    store: EventStore,
    mesh: Mesh,
    capacity: Optional[int] = None,
    tablets_per_device: int = 1,
) -> DistStore:
    """Re-shard a host EventStore's event tables onto the mesh by row hash
    (the paper's uniform random sharding) — implemented as a bulk replay
    through the distributed ingest plane: the host rows stream through
    DistIngestPlane.ingest and the device-side compaction programs build
    the sorted tablets (the former host-side NumPy scatter loop is gone)."""
    from .dist_ingest import DistIngestPlane

    t = int(np.prod([mesh.shape[a] for a in mesh.axis_names])) * tablets_per_device
    rows_k, rows_c = [], []
    for tab in store.event_tablets:
        for run in tab.snapshot_runs():
            _, rts, h = keypack.unpack_event_key(run.keys)
            rows_k.append(np.stack([rts, h], 1))
            rows_c.append(run.cols)
    if rows_k:
        rk = np.concatenate(rows_k)
        rc = np.concatenate(rows_c)
    else:
        rk = np.zeros((0, 2), np.int64)
        rc = np.zeros((0, store.schema.n_fields), np.int32)
    assign = (rk[:, 1] % t).astype(np.int64)  # hash-uniform tablet choice
    per_tablet = np.bincount(assign, minlength=t)
    cap = capacity or max(int(per_tablet.max()), 1)
    if int(per_tablet.max()) > cap:
        # An explicitly undersized capacity must fail loudly BEFORE the
        # replay: publish() no longer folds runs into the base, so the
        # device overflow counter would only trip at some later major —
        # the host-side assignment counts are exact now, use them.
        raise ValueError(
            f"tablet overflow: {int(per_tablet.max())} rows for one tablet "
            f"over capacity {cap}"
        )
    # The plane's flush triggers are exact per tablet (host-side fill
    # mirror), so fixed per-tablet buffers suffice: a tablet majors every
    # max_runs * mem_rows of ITS OWN rows — run-slab memory stays
    # O(T * max_runs * mem_rows), independent of replay size. for_store
    # binds the store's indexed fields + aggregate bucketing, so the
    # replay also builds live index postings and planner densities.
    plane = DistIngestPlane.for_store(
        store,
        mesh,
        capacity=cap,
        tablets_per_device=tablets_per_device,
        mem_rows=8192,
        max_runs=8,
        append_rows=2048,
    )
    plane.ingest(rk[:, 0].astype(np.int32), rc, assign.astype(np.int32))
    # A bulk replay is one-shot: fold everything into the base up front
    # and snapshot ONLY the base level. The replay plane's big run slabs
    # (8 slots x 8192 rows) would otherwise ride along empty in every
    # compiled read — fixed-shape level work with nothing in it. Live
    # planes (DistQueryProcessor(plane=...)) keep the full run-aware
    # snapshot; this static view has nothing unfolded to search.
    plane.compact()
    overflow = int(plane.telemetry()["overflow"].sum())
    if overflow:  # pragma: no cover — the pre-check above bounds this
        raise ValueError(f"tablet overflow: {overflow} rows over capacity {cap}")
    s = plane.state
    has_ix = len(plane.families) > 1
    return DistStore(
        rev_ts=s["ev_base_k"],
        cols=s["ev_base_c"],
        counts=s["ev_base_n"],
        mesh=mesh,
        ix_keys=s["ix_base_k"] if has_ix else None,
        ix_counts=s["ix_base_n"] if has_ix else None,
        ag_keys=s["ag_base_k"] if has_ix else None,
        ag_vals=s["ag_base_c"] if has_ix else None,
        ag_counts=s["ag_base_n"] if has_ix else None,
        agg_bucket_s=plane.agg_bucket_s if has_ix else None,
    )


def _program_eval(cols, opcodes, arg0, arg1, codesets):
    """Postfix predicate program over (R, F) codes — identical semantics
    to kernels/filter_scan (jnp form, shard-local)."""
    from ..kernels.program_eval import program_eval_rows

    return program_eval_rows(cols, opcodes, arg0, arg1, codesets)


def _merge_level_topk(rev_parts, col_parts, top_k):
    """Device-side merge of per-level top-k candidates: concatenate the
    (sentinel-padded, _I32_MAX) rev_ts slates and keep the k smallest —
    smallest rev_ts == newest row, matching per-level order."""
    all_rev = jnp.concatenate(rev_parts)
    all_cols = jnp.concatenate(col_parts)
    order = jnp.argsort(all_rev)[:top_k]
    return all_rev[order], all_cols[order]


def build_scan_step(
    mesh: Mesh,
    n_fields: int,
    prog_len: int,
    set_shape: Tuple[int, int],
    top_k: int = 128,
    runs: bool = False,
):
    """Jitted distributed scan: (store, program, t-range) -> (global count,
    per-tablet top-k newest matches). One invocation per adaptive batch.
    Each device vmaps over its local tablets (tablets_per_device may
    exceed 1 — the ingest plane's W x T sweeps size T independently of
    the mesh), then psums across the mesh.

    With runs=True the scan is RUN-AWARE: the same range-restrict +
    filter + top-k runs per LSM level (base, each sorted-run slab, the
    sealed memtable), counts sum, and the per-level top-k slates merge by
    rev_ts on device — unfolded rows are exactly as visible as the base."""
    axes = tuple(mesh.axis_names)
    specs = tablet_specs(mesh)

    def tablet_scan(*args):
        if runs:
            (rev_ts, cols, counts, run_k, run_c, run_n, mem_k, mem_c, mem_n,
             opcodes, arg0, arg1, codesets, rts_lo, rts_hi) = args
        else:
            (rev_ts, cols, counts,
             opcodes, arg0, arg1, codesets, rts_lo, rts_hi) = args

        def one(rev_l, cols_l, n, *lv):
            def level(rev, cl, nn):
                r = rev.shape[0]
                # Range restriction on sorted rev_ts: [lo, hi) via
                # searchsorted; nn masks pad rows AND stale run slots.
                a = jnp.searchsorted(rev, rts_lo, side="left")
                b = jnp.searchsorted(rev, rts_hi, side="left")
                idx = jnp.arange(r, dtype=jnp.int32)
                in_range = (idx >= a) & (idx < b) & (idx < nn)
                hit = _program_eval(cl, opcodes, arg0, arg1, codesets) & in_range
                count = hit.sum(dtype=jnp.int32)
                # Top-k newest matches (smallest rev_ts == newest).
                rank = jnp.where(hit, idx, r)
                top = jnp.sort(rank)[:top_k]
                valid = top < r
                safe = jnp.clip(top, 0, r - 1)
                out_rev = jnp.where(valid, rev[safe], jnp.int32(_I32_MAX))
                out_cols = jnp.where(valid[:, None], cl[safe], -1)
                return count, out_rev, out_cols

            count, out_rev, out_cols = level(rev_l, cols_l, n)
            if runs:
                rk, rc, rn, mk, mc, mn = lv
                rcnt, rrev, rcols = jax.vmap(level)(rk, rc, rn)
                mcnt, mrev, mcols = level(mk, mc, mn)
                count = count + rcnt.sum(dtype=jnp.int32) + mcnt
                out_rev, out_cols = _merge_level_topk(
                    [out_rev, rrev.reshape(-1), mrev],
                    [out_cols, rcols.reshape(-1, out_cols.shape[1]), mcols],
                    top_k,
                )
            out_ts = jnp.where(out_rev < jnp.int32(_I32_MAX), out_rev, INVALID_TS)
            return count, out_ts, out_cols

        if runs:
            count_l, out_ts, out_cols = jax.vmap(one)(
                rev_ts, cols, counts, run_k, run_c, run_n, mem_k, mem_c, mem_n
            )
        else:
            count_l, out_ts, out_cols = jax.vmap(one)(rev_ts, cols, counts)
        total = jax.lax.psum(count_l.sum(dtype=jnp.int32), axes)
        return total, out_ts, out_cols

    in_specs = (specs["rev_ts"], specs["cols"], specs["counts"])
    if runs:
        in_specs += _ev_level_specs(axes)
    in_specs += (
        P(None), P(None), P(None), P(None, None),  # program: replicated
        P(), P(),
    )
    smapped = shard_map(
        tablet_scan,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P(axes, None), P(axes, None, None)),
        check_vma=False,
    )
    return jax.jit(smapped)


def _segment_aggregate(r_rev, r_cols, hit, fids, strides, n_groups, bucket_s,
                       bucket_lo, op, value_fid, value_table, identity):
    """Fused dense segment aggregation over one slab of gathered rows —
    the CombinerIterator body shared by the scan-time and index-time
    aggregate steps. Junk codes on masked rows clamp into range; their
    contribution is the identity anyway."""
    r = r_rev.shape[0]
    gid = jnp.zeros((r,), jnp.int32)
    for fid, stride in zip(fids, strides):
        gid = gid + r_cols[:, fid] * jnp.int32(stride)
    if bucket_s is not None:
        ts_l = jnp.int32(keypack.TS_MAX) - r_rev
        gid = gid + ts_l // jnp.int32(bucket_s) - bucket_lo
    gid = jnp.clip(gid, 0, n_groups - 1)
    if value_fid is not None:
        codes = jnp.clip(r_cols[:, value_fid], 0, value_table.shape[0] - 1)
        val = value_table[codes]
    else:
        val = jnp.ones((r,), jnp.int32)
    if op in ("count", "sum"):
        # Sums accumulate in int64, matching the host iterator stack — a
        # tablet of large int32 values must not wrap before the psum
        # (min/max are order statistics).
        contrib = jnp.where(hit, val.astype(jnp.int64), jnp.int64(identity))
        aggs = jax.ops.segment_sum(contrib, gid, num_segments=n_groups)
    elif op == "min":
        contrib = jnp.where(hit, val, jnp.int32(identity))
        aggs = jax.ops.segment_min(contrib, gid, num_segments=n_groups)
    else:
        contrib = jnp.where(hit, val, jnp.int32(identity))
        aggs = jax.ops.segment_max(contrib, gid, num_segments=n_groups)
    cnts = jax.ops.segment_sum(hit.astype(jnp.int64), gid, num_segments=n_groups)
    return aggs, cnts


def _fold_runs_axis(raggs, rcnts, op):
    """Fold the leading run-slot axis of vmapped per-run (aggs, cnts)
    partials into one level part — same dispatch as the cross-level merge
    (counts always add; only the aggregate folds per op)."""
    if op in ("count", "sum"):
        return raggs.sum(axis=0), rcnts.sum(axis=0)
    if op == "min":
        return raggs.min(axis=0), rcnts.sum(axis=0)
    return raggs.max(axis=0), rcnts.sum(axis=0)


def _combine_level_aggs(parts, op):
    """Merge per-level (aggs, cnts) partials: rows are disjoint across
    levels, so sum/count add and min/max fold elementwise."""
    aggs_parts = [a for a, _ in parts]
    cnts = sum(c for _, c in parts)
    if op in ("count", "sum"):
        aggs = sum(aggs_parts)
    elif op == "min":
        aggs = aggs_parts[0]
        for a in aggs_parts[1:]:
            aggs = jnp.minimum(aggs, a)
    else:
        aggs = aggs_parts[0]
        for a in aggs_parts[1:]:
            aggs = jnp.maximum(aggs, a)
    return aggs, cnts


def build_aggregate_step(
    mesh: Mesh,
    fids: Tuple[int, ...],
    strides: Tuple[int, ...],
    n_groups: int,
    n_buckets: int,
    bucket_s: Optional[int],
    op: str,
    value_fid: Optional[int],
    runs: bool = False,
):
    """Jitted distributed scan-time aggregation: the iterator stack's
    terminal CombinerIterator lowered into the mesh program. Each tablet
    evaluates the fused filter + dense segment aggregation locally — per
    LSM level when runs=True, partials summed across levels (rows are
    disjoint between levels; the agg FAMILY only folds duplicates at
    major, but this step aggregates event rows, which never duplicate) —
    then the dense group-id space (mixed-radix codes x time buckets, see
    ResolvedGrouping) makes the cross-tablet merge a single psum (sum /
    count) or pmin/pmax — no gather of raw rows ever happens."""
    axes = tuple(mesh.axis_names)
    specs = tablet_specs(mesh)
    int32_max = jnp.iinfo(jnp.int32).max
    int32_min = jnp.iinfo(jnp.int32).min
    identity = {"count": 0, "sum": 0, "min": int32_max, "max": int32_min}[op]

    def tablet_agg(*args):
        if runs:
            (rev_ts, cols, counts, run_k, run_c, run_n, mem_k, mem_c, mem_n,
             opcodes, arg0, arg1, codesets, value_table,
             rts_lo, rts_hi, bucket_lo) = args
        else:
            (rev_ts, cols, counts,
             opcodes, arg0, arg1, codesets, value_table,
             rts_lo, rts_hi, bucket_lo) = args

        def one(rev_l, cols_l, n, *lv):
            def level(rev, cl, nn):
                r = rev.shape[0]
                a = jnp.searchsorted(rev, rts_lo, side="left")
                b = jnp.searchsorted(rev, rts_hi, side="left")
                idx = jnp.arange(r, dtype=jnp.int32)
                in_range = (idx >= a) & (idx < b) & (idx < nn)
                hit = _program_eval(cl, opcodes, arg0, arg1, codesets) & in_range
                return _segment_aggregate(
                    rev, cl, hit, fids, strides, n_groups, bucket_s,
                    bucket_lo, op, value_fid, value_table, identity,
                )

            parts = [level(rev_l, cols_l, n)]
            if runs:
                rk, rc, rn, mk, mc, mn = lv
                raggs, rcnts = jax.vmap(level)(rk, rc, rn)
                parts.append(_fold_runs_axis(raggs, rcnts, op))
                parts.append(level(mk, mc, mn))
            return _combine_level_aggs(parts, op)

        # Local tablets first (vmap + reduce), then one mesh collective.
        if runs:
            aggs_l, cnts_l = jax.vmap(one)(
                rev_ts, cols, counts, run_k, run_c, run_n, mem_k, mem_c, mem_n
            )
        else:
            aggs_l, cnts_l = jax.vmap(one)(rev_ts, cols, counts)
        if op in ("count", "sum"):
            aggs = jax.lax.psum(aggs_l.sum(axis=0), axes)
        elif op == "min":
            aggs = jax.lax.pmin(aggs_l.min(axis=0), axes)
        else:
            aggs = jax.lax.pmax(aggs_l.max(axis=0), axes)
        cnts = jax.lax.psum(cnts_l.sum(axis=0), axes)
        return aggs, cnts

    in_specs = (specs["rev_ts"], specs["cols"], specs["counts"])
    if runs:
        in_specs += _ev_level_specs(axes)
    in_specs += (
        P(None), P(None), P(None), P(None, None),  # program: replicated
        P(None),  # value table: replicated
        P(), P(), P(),
    )
    smapped = shard_map(
        tablet_agg,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(None), P(None)),
        check_vma=False,
    )
    return jax.jit(smapped)


def _posting_slabs(ik_l, ix_lv, cond_lo, cond_hi, n_conds, max_postings, runs):
    """Per-condition candidate rev_ts slabs from EVERY index level.

    For one tablet: the postings for condition i over the batch's rev_ts
    range are one contiguous slice of each sorted index level (two binary
    searches per level, clamped by the level's live count — run slots can
    hold stale rows past run_n after a major). Each level contributes up
    to min(max_postings, level size) newest-first rev_ts values (a small
    level can't yield more postings than it holds); the per-level slates
    sort into one slab per condition. Returns (slabs (n_conds, S),
    overflow) where S sums the per-level caps."""

    def posting(ik, nn, lo_i, hi_i):
        ci = ik.shape[0]
        cap = min(max_postings, ci)  # static per level
        a = jnp.minimum(jnp.searchsorted(ik, lo_i, side="left").astype(jnp.int32), nn)
        b = jnp.minimum(jnp.searchsorted(ik, hi_i, side="left").astype(jnp.int32), nn)
        cnt = b - a
        j = jnp.arange(cap, dtype=jnp.int32)
        valid = j < cnt
        kk = ik[jnp.clip(a + j, 0, ci - 1)]
        rts = jnp.where(
            valid, (kk & jnp.int64(keypack.TS_MAX)).astype(jnp.int32),
            jnp.int32(_I32_MAX),
        )
        return rts, jnp.maximum(cnt - jnp.int32(cap), 0)

    def cond_slab(i):
        s0, over = posting(ik_l, jnp.int32(ik_l.shape[0]), cond_lo[i], cond_hi[i])
        if runs:
            xrk, xrn, xmk, xmn = ix_lv
            sr, orr = jax.vmap(lambda k, nr: posting(k, nr, cond_lo[i], cond_hi[i]))(
                xrk, xrn
            )
            sm, om = posting(xmk, xmn, cond_lo[i], cond_hi[i])
            slab = jnp.sort(jnp.concatenate([s0, sr.reshape(-1), sm]))
            over = over + orr.sum() + om
        else:
            slab = s0
        return slab, over

    slabs, over = jax.vmap(cond_slab)(jnp.arange(n_conds, dtype=jnp.int32))
    return slabs, over.sum()


def _combine_postings(slabs, combine, n_conds):
    """Device-side key-set combine (paper Fig 2): k-way intersect via
    merge_intersect membership searches (AND) or a sorted merge (OR).
    Returns (cand sorted ascending, live mask) — duplicates masked out,
    since equal rev_ts candidates expand to the same base rows."""
    from ..kernels.merge_intersect import member_mask_keys

    if combine == "intersect":
        cand = slabs[0]
        keep = cand < jnp.int32(_I32_MAX)
        for i in range(1, n_conds):
            keep &= member_mask_keys(cand, slabs[i])
        cand = jnp.sort(jnp.where(keep, cand, jnp.int32(_I32_MAX)))
    else:
        cand = jnp.sort(slabs.reshape(-1))
    is_dup = jnp.concatenate([jnp.zeros((1,), bool), cand[1:] == cand[:-1]])
    live = (cand < jnp.int32(_I32_MAX)) & ~is_dup
    return cand, live


def _expand_levels(consume, cand, live, rev_l, cols_l, ev_lv, max_rows, runs):
    """Expand the candidate rev_ts set against EVERY event level and feed
    each level's gathered row slab to `consume(r_rev, r_cols, valid_m)`.

    Per level: candidate j covers rows [lo_pos[j], hi_pos[j]) by binary
    search (clamped by the level's live count — stale run slots), and the
    prefix-sum expansion maps output slot m back through one binary
    search; rows come out ascending in rev_ts (newest first). The slab is
    min(max_rows, level size) — a run or sealed-mem level can never yield
    more rows than it holds, so the compiled gather + predicate work per
    small level is bounded by the level, not the global cap. Returns
    (outs, totals, truncs), each as (base, runs | None, mem | None) with
    runs carrying a leading K axis — the caller merges the outs and sums
    totals/truncs."""
    cc = cand.shape[0]

    def expand(rev, cl, nn):
        r = rev.shape[0]
        cap = min(max_rows, r)  # static per level
        lo_pos = jnp.minimum(
            jnp.searchsorted(rev, cand, side="left").astype(jnp.int32), nn
        )
        hi_pos = jnp.minimum(
            jnp.searchsorted(rev, cand, side="right").astype(jnp.int32), nn
        )
        cnt_rows = jnp.where(live, hi_pos - lo_pos, 0)
        offs = jnp.cumsum(cnt_rows)
        total = offs[-1]
        start = offs - cnt_rows
        m = jnp.arange(cap, dtype=jnp.int32)
        j = jnp.searchsorted(offs, m, side="right").astype(jnp.int32)
        jc = jnp.clip(j, 0, cc - 1)
        row_idx = lo_pos[jc] + (m - start[jc])
        valid_m = m < total
        safe = jnp.clip(row_idx, 0, r - 1)
        r_rev = jnp.where(valid_m, rev[safe], jnp.int32(_I32_MAX))
        r_cols = jnp.where(valid_m[:, None], cl[safe], -1)
        trunc = jnp.maximum(total - jnp.int32(cap), 0)
        return consume(r_rev, r_cols, valid_m), total, trunc

    base_out, base_total, base_trunc = expand(
        rev_l, cols_l, jnp.int32(rev_l.shape[0])
    )
    if not runs:
        return (base_out, None, None), (base_total, None, None), (base_trunc, None, None)
    rk, rc, rn, mk, mc, mn = ev_lv
    runs_out, runs_total, runs_trunc = jax.vmap(expand)(rk, rc, rn)
    mem_out, mem_total, mem_trunc = expand(mk, mc, mn)
    return (
        (base_out, runs_out, mem_out),
        (base_total, runs_total, mem_total),
        (base_trunc, runs_trunc, mem_trunc),
    )


def _sum_levels(parts):
    """Sum a (base, runs | None, mem | None) scalar triple — runs carries
    the K axis."""
    base, run_part, mem_part = parts
    total = base
    if run_part is not None:
        total = total + run_part.sum()
    if mem_part is not None:
        total = total + mem_part
    return total


def build_index_step(
    mesh: Mesh,
    n_conds: int,
    combine: str,
    prog_len: int,
    set_shape: Tuple[int, int],
    top_k: int = 128,
    max_postings: int = 2048,
    max_rows: int = 4096,
    runs: bool = False,
):
    """Jitted distributed index scan — the paper's winning batched-index
    scheme lowered to the mesh (Fig 2: index lookups -> key-set combine ->
    row fetch -> residual filter, all device-side), RUN-AWARE: postings
    come from every index level (base + run slabs + sealed memtable) and
    candidates expand against every event level, so unfolded rows are
    index-visible with no fold at publish.

    Per tablet, per condition, per level: the postings for (field, value)
    over the batch's rev_ts range are ONE contiguous slice of that sorted
    level (two binary searches), gathered into a fixed max_postings slab;
    the per-level slates sort into one slab per condition. The slabs
    combine device-side — k-way intersect via kernels/merge_intersect
    membership searches (AND), or a sorted merge (OR). Candidate rev_ts
    values then expand to rows of each event level by binary search +
    prefix-sum expansion, and the predicate program runs ONLY on the
    gathered candidate rows (max_rows per level) — never on the full
    tablet, which is the whole latency win over filter-scan.

    Correctness does not rest on the index: the FULL query tree re-checks
    every candidate row, so rev_ts collisions between distinct rows cost a
    wasted candidate, never a wrong result (and the ix family's
    dedup-at-major never loses a row for the same reason). Slab overflow
    is reported in the `truncated` output; the executor falls back to the
    exact filter-scan step for that batch (adaptive batching keeps
    per-batch result sets small, so this is rare).

    Returns (global_count, per-tablet top-k (ts, cols), truncated,
    candidate_rows) — the last is the diagnostic 'index entries actually
    used' count (psum'd)."""
    axes = tuple(mesh.axis_names)
    specs = tablet_specs(mesh)

    # Base slabs are ALWAYS sentinel-padded past *_base_n (init, merges,
    # and non-donated majors all preserve it) and every probe key is below
    # the sentinel, so base binary searches never land in the pad tail.
    # Run slots DO hold stale rows past run_n after a major — the level
    # helpers clamp by the live counts.
    def tablet_ix(*args):
        if runs:
            (rev_ts, cols, ix_keys,
             run_k, run_c, run_n, mem_k, mem_c, mem_n,
             ix_run_k, ix_run_n, ix_mem_k, ix_mem_n,
             opcodes, arg0, arg1, codesets, cond_lo, cond_hi) = args
        else:
            (rev_ts, cols, ix_keys,
             opcodes, arg0, arg1, codesets, cond_lo, cond_hi) = args

        def one(rev_l, cols_l, ik_l, *lv):
            ev_lv, ix_lv = (lv[:6], lv[6:]) if runs else (None, None)
            slabs, post_over = _posting_slabs(
                ik_l, ix_lv, cond_lo, cond_hi, n_conds, max_postings, runs
            )
            cand, live = _combine_postings(slabs, combine, n_conds)

            def consume(r_rev, r_cols, valid_m):
                # Exactness: the FULL tree re-checks candidates (residual
                # AND indexed conditions), so over-approximate candidate
                # sets are filtered here, at candidate cardinality.
                n = r_rev.shape[0]  # this level's slab size (<= max_rows)
                hit = _program_eval(r_cols, opcodes, arg0, arg1, codesets) & valid_m
                count = hit.sum(dtype=jnp.int32)
                m = jnp.arange(n, dtype=jnp.int32)
                rank = jnp.where(hit, m, jnp.int32(n))
                top = jnp.sort(rank)[:top_k]
                tvalid = top < n
                tsafe = jnp.clip(top, 0, n - 1)
                out_rev = jnp.where(tvalid, r_rev[tsafe], jnp.int32(_I32_MAX))
                out_cols = jnp.where(tvalid[:, None], r_cols[tsafe], -1)
                return count, out_rev, out_cols

            outs, totals, truncs = _expand_levels(
                consume, cand, live, rev_l, cols_l, ev_lv, max_rows, runs
            )
            (c0, rev0, cols0), runs_out, mem_out = outs
            count = c0
            rev_parts, col_parts = [rev0], [cols0]
            if runs:
                cr, revr, colsr = runs_out
                cm, revm, colsm = mem_out
                count = count + cr.sum(dtype=jnp.int32) + cm
                rev_parts += [revr.reshape(-1), revm]
                col_parts += [colsr.reshape(-1, cols0.shape[1]), colsm]
            out_rev, out_cols = _merge_level_topk(rev_parts, col_parts, top_k)
            out_ts = jnp.where(out_rev < jnp.int32(_I32_MAX), out_rev, INVALID_TS)
            trunc = post_over + _sum_levels(truncs)
            return count, out_ts, out_cols, trunc, _sum_levels(totals)

        if runs:
            count_l, ts_l, cols_l, trunc_l, cand_l = jax.vmap(one)(
                rev_ts, cols, ix_keys,
                run_k, run_c, run_n, mem_k, mem_c, mem_n,
                ix_run_k, ix_run_n, ix_mem_k, ix_mem_n,
            )
        else:
            count_l, ts_l, cols_l, trunc_l, cand_l = jax.vmap(one)(
                rev_ts, cols, ix_keys
            )
        total = jax.lax.psum(count_l.sum(dtype=jnp.int32), axes)
        truncated = jax.lax.psum(trunc_l.sum(dtype=jnp.int32), axes)
        candidates = jax.lax.psum(cand_l.sum(dtype=jnp.int32), axes)
        return total, ts_l, cols_l, truncated, candidates

    in_specs = (specs["rev_ts"], specs["cols"], P(axes, None))
    if runs:
        in_specs += _ev_level_specs(axes) + _ix_level_specs(axes)
    in_specs += (
        P(None), P(None), P(None), P(None, None),  # program: replicated
        P(None), P(None),  # per-condition packed key ranges
    )
    smapped = shard_map(
        tablet_ix,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P(axes, None), P(axes, None, None), P(), P()),
        check_vma=False,
    )
    return jax.jit(smapped)


def build_index_aggregate_step(
    mesh: Mesh,
    n_conds: int,
    combine: str,
    prog_len: int,
    set_shape: Tuple[int, int],
    fids: Tuple[int, ...],
    strides: Tuple[int, ...],
    n_groups: int,
    bucket_s: Optional[int],
    op: str,
    value_fid: Optional[int],
    max_postings: int = 2048,
    max_rows: int = 4096,
    runs: bool = False,
):
    """Jitted index-driven aggregation: the batched-index candidate gather
    of build_index_step feeding the CombinerIterator segment aggregation
    of build_aggregate_step — selective aggregates combine over ONLY the
    gathered candidate rows instead of filter-scanning the full tablet.
    Same exactness contract: the FULL tree re-checks every candidate, and
    slab overflow reports in `truncated` so the caller can fall back to
    the exact scan-time aggregation.

    Returns (aggs (n_groups,), cnts (n_groups,), truncated, candidates)."""
    axes = tuple(mesh.axis_names)
    specs = tablet_specs(mesh)
    int32_max = jnp.iinfo(jnp.int32).max
    int32_min = jnp.iinfo(jnp.int32).min
    identity = {"count": 0, "sum": 0, "min": int32_max, "max": int32_min}[op]

    def tablet_ixagg(*args):
        if runs:
            (rev_ts, cols, ix_keys,
             run_k, run_c, run_n, mem_k, mem_c, mem_n,
             ix_run_k, ix_run_n, ix_mem_k, ix_mem_n,
             opcodes, arg0, arg1, codesets, value_table,
             cond_lo, cond_hi, bucket_lo) = args
        else:
            (rev_ts, cols, ix_keys,
             opcodes, arg0, arg1, codesets, value_table,
             cond_lo, cond_hi, bucket_lo) = args

        def one(rev_l, cols_l, ik_l, *lv):
            ev_lv, ix_lv = (lv[:6], lv[6:]) if runs else (None, None)
            slabs, post_over = _posting_slabs(
                ik_l, ix_lv, cond_lo, cond_hi, n_conds, max_postings, runs
            )
            cand, live = _combine_postings(slabs, combine, n_conds)

            def consume(r_rev, r_cols, valid_m):
                hit = _program_eval(r_cols, opcodes, arg0, arg1, codesets) & valid_m
                return _segment_aggregate(
                    r_rev, r_cols, hit, fids, strides, n_groups, bucket_s,
                    bucket_lo, op, value_fid, value_table, identity,
                )

            outs, totals, truncs = _expand_levels(
                consume, cand, live, rev_l, cols_l, ev_lv, max_rows, runs
            )
            base_out, runs_out, mem_out = outs
            parts = [base_out]
            if runs:
                raggs, rcnts = runs_out
                parts.append(_fold_runs_axis(raggs, rcnts, op))
                parts.append(mem_out)
            aggs, cnts = _combine_level_aggs(parts, op)
            trunc = post_over + _sum_levels(truncs)
            return aggs, cnts, trunc, _sum_levels(totals)

        if runs:
            aggs_l, cnts_l, trunc_l, cand_l = jax.vmap(one)(
                rev_ts, cols, ix_keys,
                run_k, run_c, run_n, mem_k, mem_c, mem_n,
                ix_run_k, ix_run_n, ix_mem_k, ix_mem_n,
            )
        else:
            aggs_l, cnts_l, trunc_l, cand_l = jax.vmap(one)(rev_ts, cols, ix_keys)
        if op in ("count", "sum"):
            aggs = jax.lax.psum(aggs_l.sum(axis=0), axes)
        elif op == "min":
            aggs = jax.lax.pmin(aggs_l.min(axis=0), axes)
        else:
            aggs = jax.lax.pmax(aggs_l.max(axis=0), axes)
        cnts = jax.lax.psum(cnts_l.sum(axis=0), axes)
        truncated = jax.lax.psum(trunc_l.sum(dtype=jnp.int32), axes)
        candidates = jax.lax.psum(cand_l.sum(dtype=jnp.int32), axes)
        return aggs, cnts, truncated, candidates

    in_specs = (specs["rev_ts"], specs["cols"], P(axes, None))
    if runs:
        in_specs += _ev_level_specs(axes) + _ix_level_specs(axes)
    in_specs += (
        P(None), P(None), P(None), P(None, None),  # program: replicated
        P(None),  # value table: replicated
        P(None), P(None), P(),  # cond ranges + bucket origin
    )
    smapped = shard_map(
        tablet_ixagg,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(None), P(None), P(), P()),
        check_vma=False,
    )
    return jax.jit(smapped)


def build_density_step(mesh: Mesh, runs: bool = False):
    """Jitted distributed density read for the query planner: total count
    over one packed aggregate-key range — per-tablet searchsorted + masked
    sum per LSM level (the agg family folds duplicate keys only at major
    compaction, so unfolded levels may repeat a key: the counts are
    additive by construction and SUM across levels), merged with a single
    psum. This is how plan_query's d_i estimates come off the mesh instead
    of the host aggregate table."""
    axes = tuple(mesh.axis_names)

    def fn(*args):
        if runs:
            (ag_keys, ag_vals, ag_run_k, ag_run_c, ag_run_n,
             ag_mem_k, ag_mem_c, ag_mem_n, lo, hi) = args
        else:
            ag_keys, ag_vals, lo, hi = args

        def level(k_l, v_l, nn):
            ca = k_l.shape[0]
            a = jnp.searchsorted(k_l, lo, side="left")
            b = jnp.searchsorted(k_l, hi, side="left")
            idx = jnp.arange(ca)
            in_r = (idx >= a) & (idx < b) & (idx < nn)
            return jnp.where(in_r, v_l[:, 0], 0).sum()

        def one(k_l, v_l, *lv):
            total = level(k_l, v_l, jnp.int32(k_l.shape[0]))
            if runs:
                rk, rc, rn, mk, mc, mn = lv
                total = total + jax.vmap(level)(rk, rc, rn).sum()
                total = total + level(mk, mc, mn)
            return total

        if runs:
            local = jax.vmap(one)(
                ag_keys, ag_vals, ag_run_k, ag_run_c, ag_run_n,
                ag_mem_k, ag_mem_c, ag_mem_n,
            )
        else:
            local = jax.vmap(one)(ag_keys, ag_vals)
        return jax.lax.psum(local.sum(), axes)

    in_specs = (P(axes, None), P(axes, None, None))
    if runs:
        in_specs += _ag_level_specs(axes)
    in_specs += (P(), P())
    smapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(smapped)


@dataclass
class DistBatch:
    """One batch's result from the distributed executor: the exact global
    matching-row count plus the per-tablet top-k newest rows (BatchScanner
    semantics: unordered across tablets, newest-first within). lo/hi are
    the adaptive batch's time sub-range when stepped through a QueryRun
    (the serve plane streams these to clients and checks monotonicity)."""

    count: int
    ts: np.ndarray
    cols: np.ndarray
    lo: float = 0.0
    hi: float = 0.0

    @property
    def n(self) -> int:
        return self.count

    @property
    def nbytes(self) -> int:
        return self.ts.nbytes + self.cols.nbytes


class _PinnedSource:
    """plan_query density source bound to ONE published snapshot: an
    in-flight query's planning reads d_i from the same LSM state its
    batches will execute against, even while publishes and background
    compactions race the query (per-call isolation for the serve plane)."""

    def __init__(self, proc: "DistQueryProcessor", dist: DistStore, profile=None):
        self._proc = proc
        self._dist = dist
        self._profile = profile  # serve_db QueryProfile: density stage clock

    @property
    def schema(self):
        return self._proc.store.schema

    @property
    def dictionaries(self):
        return self._proc.store.dictionaries

    def agg_count(self, field: str, value: str, t_start: int, t_stop: int) -> int:
        if self._profile is None:
            return self._proc._agg_count_on(self._dist, field, value, t_start, t_stop)
        t0 = time.perf_counter()
        out = self._proc._agg_count_on(self._dist, field, value, t_start, t_stop)
        self._profile.density_acc_s += time.perf_counter() - t0
        return out


class QueryRun:
    """One planned query pinned to one published snapshot, stepped one
    adaptive batch at a time — the re-entrant form of
    DistQueryProcessor.execute().

    The serve plane's scheduler (repro.serve_db) interleaves many
    sessions' QueryRuns under a device lock: step() executes exactly ONE
    Alg-2 batch (one device program in filter mode; index mode adds the
    filter-scan redo only on slab overflow) and feeds the observed
    (runtime, rows) back into the run's own AdaptiveBatcher. Nothing here
    mutates processor state beyond the lock-guarded jit step caches, so
    any number of runs step concurrently; and because the snapshot is
    pinned at construction — published levels are stable, compaction
    programs never donate their buffers — a background compact() or a
    concurrent publish can never change this run's results mid-flight."""

    def __init__(
        self,
        proc: "DistQueryProcessor",
        tree,
        t_start: int,
        t_stop: int,
        use_index: bool = True,
        batched: bool = True,
        stats=None,
        profile=None,
    ):
        self.proc = proc
        self.tree = tree
        self.t_start = t_start
        self.t_stop = t_stop
        self.stats = stats
        # serve_db QueryProfile (or None): the execution layer adds its
        # density reads and device-program sections into the profile's
        # accumulators so the serve plane can tile TTFR into stages.
        self.profile = profile
        self.dist = proc._sync()  # pinned for the whole run
        source = (
            _PinnedSource(proc, self.dist, profile=profile)
            if self.dist.has_index else proc.store
        )
        with span("query.plan", cat="query") as sp:
            self.plan = plan_query(
                source, tree, t_start, t_stop, w=proc.w,
                use_index=use_index and self.dist.has_index,
            )
            sp.set(mode=self.plan.mode)
        if stats is not None:
            stats.plan = self.plan
        self._empty = self.plan.mode == "empty"
        self._single_done = False
        if batched and not self._empty:
            rps = proc.store.rows_per_second()
            self.batcher: Optional[AdaptiveBatcher] = AdaptiveBatcher(
                t_start=t_start, t_stop=t_stop, b0=rps and 10.0 / rps
            )
        else:
            self.batcher = None

    @property
    def done(self) -> bool:
        if self._empty:
            return True
        if self.batcher is None:
            return self._single_done
        return self.batcher.done

    # reprolint: hot-path — one serve-plane turn == N of these steps
    def step(self) -> Optional[DistBatch]:
        """Execute the next adaptive batch and return it (lo/hi carry the
        batch's time sub-range); None once the run is done — provably
        empty plans never dispatch a device program at all."""
        if self.done:
            return None
        if self.batcher is None:
            lo, hi = float(self.t_start), float(self.t_stop)
        else:
            lo, hi = self.batcher.next_range()
        t0 = time.perf_counter()
        with span("query.step", cat="query", mode=self.plan.mode) as sp:
            blk = self.proc._exec_range(
                self.plan, self.tree, int(lo), int(hi), self.stats,
                dist=self.dist, profile=self.profile,
            )
            sp.set(rows=int(blk.count))
        runtime = time.perf_counter() - t0
        if self.batcher is None:
            self._single_done = True
        else:
            self.batcher.update(runtime, blk.count)
        if self.stats is not None:
            self.stats.batches += 1
            self.stats.rows += blk.count
            self.stats.batch_log.append((lo, hi, runtime, blk.count))
        blk.lo, blk.hi = float(lo), float(hi)
        return blk


class DistQueryProcessor:
    """Planner-driven, adaptively batched queries over the mesh — all four
    of the paper's §IV-B schemes (scan / batched_scan / index /
    batched_index) running distributed.

    With `plane=` (a DistIngestPlane), every query first syncs to the
    plane's latest published snapshot — rows written through
    DistBatchWriter become query-visible with no host round trip: publish
    is a sealed-memtable sort plus a metadata flip (never a fold into the
    base — every read here searches base + runs + sealed memtable), and a
    no-op when nothing was ingested. Planes that maintain the
    index/aggregate families (DistIngestPlane.for_store /
    from_event_store) additionally enable the index schemes: plan_query
    reads densities from the distributed aggregate tablets (agg_count, a
    psum over all levels) and index-mode plans execute as build_index_step
    programs — including selective AGGREGATES, which combine over the
    gathered index candidates only (build_index_aggregate_step).
    Index-less stores fall back to filter-scan for every plan."""

    def __init__(
        self,
        store: EventStore,
        dist: Optional[DistStore] = None,
        top_k: int = 128,
        plane=None,
        w: float = 10.0,
        index_postings: int = 2048,
        index_rows: int = 4096,
    ):
        if dist is None:
            if plane is None:
                raise ValueError("need dist= or plane=")
            dist = plane.publish()
        self.store = store
        self.dist = dist
        self.plane = plane
        self.top_k = top_k
        self.w = w
        self.index_postings = index_postings
        self.index_rows = index_rows
        self._step_cache: Dict[Tuple, object] = {}  # guarded-by: _cache_lock
        # Re-entrancy: many serve-plane sessions step queries through ONE
        # processor concurrently. The cache lock guards the jit-step dict;
        # per-query state (plan, batcher, stats, the pinned snapshot)
        # lives in each QueryRun, never on self. OwnedLock (not a bare
        # threading.Lock) so first-trace stalls show up attributed in the
        # occupancy report next to the plane and device locks.
        self._cache_lock = OwnedLock("step_cache_lock")

    def _sync(self) -> DistStore:
        """Refresh to the plane's latest published snapshot and return it.
        Callers pin the RETURNED snapshot for the duration of one
        operation (self.dist may be re-flipped by a concurrent caller at
        any time; a published snapshot itself is immutable)."""
        if self.plane is not None:
            self.dist = self.plane.publish()
        return self.dist

    # ------------------------------------------------- level input helpers
    @staticmethod
    def _ev_levels(d: DistStore) -> Tuple[jax.Array, ...]:
        return (d.run_rev_ts, d.run_cols, d.run_counts,
                d.mem_rev_ts, d.mem_cols, d.mem_counts)

    @staticmethod
    def _ix_levels(d: DistStore) -> Tuple[jax.Array, ...]:
        return (d.ix_run_k, d.ix_run_n, d.ix_mem_k, d.ix_mem_n)

    @staticmethod
    def _ag_levels(d: DistStore) -> Tuple[jax.Array, ...]:
        return (d.ag_run_k, d.ag_run_c, d.ag_run_n,
                d.ag_mem_k, d.ag_mem_c, d.ag_mem_n)

    def _cached_step(self, key: Tuple, build):
        with self._cache_lock.hold("step_cache"):
            if key not in self._step_cache:
                self._step_cache[key] = build()
            return self._step_cache[key]

    # ------------------------------------------------- planner density source
    # plan_query duck-types its store argument: it needs .schema,
    # .dictionaries and .agg_count. Exposing them here makes the processor
    # itself the density source, with d_i read from the mesh.
    @property
    def schema(self):
        return self.store.schema

    @property
    def dictionaries(self):
        return self.store.dictionaries

    # reprolint: hot-path
    def agg_count(self, field: str, value: str, t_start: int, t_stop: int) -> int:
        """Occurrences of field=value in the bucketed time range, from the
        DISTRIBUTED aggregate tablets (psum of per-tablet, per-level
        counts) — the planner's d_i, served by the mesh instead of the
        host store, fresh through unfolded runs."""
        return self._agg_count_on(self._sync(), field, value, t_start, t_stop)

    # reprolint: hot-path — planning reads densities per condition per query
    def _agg_count_on(self, d: DistStore, field: str, value: str,
                      t_start: int, t_stop: int) -> int:
        """agg_count against ONE pinned snapshot (no re-publish): planning
        for an in-flight QueryRun reads densities from the same LSM state
        its batches will execute against. Memoized PER SNAPSHOT (a
        published DistStore is immutable, so a density read never goes
        stale within it): concurrent sessions planning the same
        conditions — the common case on the serve plane — pay the device
        read once, which is most of a follower query's
        time-to-first-result."""
        if not d.has_index:
            return self.store.agg_count(field, value, t_start, t_stop)
        cache = d.density_cache
        ckey = (field, value, int(t_start), int(t_stop))
        hit = cache.get(ckey)
        if hit is not None:
            return hit
        if d.groups is not None:
            # Composite snapshot: densities sum over the disjoint tablet
            # groups. Each recursion memoizes in ITS sub-store's cache —
            # sub-snapshots alias across publishes when their group is
            # clean, so an untouched group's densities stay warm even as
            # busy groups re-seal (the composite-level memo above only
            # lives as long as this exact composition).
            out = sum(
                self._agg_count_on(sub, field, value, t_start, t_stop)
                for sub in d.groups
            )
            cache[ckey] = out
            return out
        code = self.store.dictionaries[field].lookup(value)
        if code is None:
            cache[ckey] = 0
            return 0
        fid = self.store.schema.field_id(field)
        bs = d.agg_bucket_s
        b0 = int(t_start) // bs
        b1 = int(t_stop) // bs
        # keypack packs host-side numpy scalars — no device value, no sync.
        lo = int(keypack.pack_agg_key(fid, code, b0))  # reprolint: disable=no-sync-in-hot-path
        hi = int(keypack.pack_agg_key(fid, code, b1)) + 1  # reprolint: disable=no-sync-in-hot-path
        step = self._cached_step(
            ("density", d.has_runs),
            lambda: build_density_step(d.mesh, runs=d.has_runs),
        )
        args = (d.ag_keys, d.ag_vals)
        if d.has_runs:
            args += self._ag_levels(d)
        with span("query.density", cat="query", field=field, value=value) as sp:
            out = int(sp.fence(step(*args, jnp.int64(lo), jnp.int64(hi))))
        cache[ckey] = out
        return out

    def _step(self, prog: FilterProgram, d: DistStore):
        from ..kernels.filter_scan.ops import pad_program

        opc, a0, a1, cs = pad_program(prog)
        step = self._cached_step(
            (len(opc), cs.shape, d.has_runs),
            lambda: build_scan_step(
                d.mesh, self.store.schema.n_fields, len(opc), cs.shape,
                self.top_k, runs=d.has_runs,
            ),
        )
        return step, (opc, a0, a1, cs)

    # reprolint: hot-path — the per-batch device program of every scan scheme
    def scan_range(self, tree, t0: int, t1: int, dist: Optional[DistStore] = None,
                   profile=None):
        """One range scan across all tablets and all LSM levels. Returns
        (global_count, top-k rows per tablet as (ts, cols) numpy arrays).
        `dist` pins an already-published snapshot (QueryRun); default
        syncs to the plane's latest. `profile` (serve_db QueryProfile)
        accumulates the device-program section into device_acc_s."""
        d = dist if dist is not None else self._sync()
        if d.groups is not None:
            # Composite snapshot: one device program per tablet group
            # (each group is its own mesh-wide shard_map — same compiled
            # step, cached on identical shapes), counts summed and top-k
            # slates concatenated (BatchScanner semantics are unordered
            # across tablets, so across groups too).
            total = 0
            ts_parts, col_parts = [], []
            for sub in d.groups:
                c, ts, cols = self.scan_range(tree, t0, t1, dist=sub, profile=profile)
                total += c
                ts_parts.append(ts)
                col_parts.append(cols)
            return total, np.concatenate(ts_parts), np.concatenate(col_parts)
        prog = compile_tree(self.store, tree)
        step, (opc, a0, a1, cs) = self._step(prog, d)
        rts_lo = jnp.int32(keypack.rev_ts(t1))
        rts_hi = jnp.int32(keypack.rev_ts(t0) + 1)
        args = (d.rev_ts, d.cols, d.counts)
        if d.has_runs:
            args += self._ev_levels(d)
        # Materialize INSIDE the span, each wait fenced: the span record
        # is emitted at __exit__, so a sync after the block would charge
        # this batch's device wait to nothing (and np.asarray on a device
        # array is exactly such a sync) — found by reprolint's
        # no-sync-in-hot-path rule.
        tdev = time.perf_counter()
        with span("query.scan_range", cat="query") as sp:
            total, top_ts, top_cols = step(
                *args,
                jnp.asarray(opc), jnp.asarray(a0), jnp.asarray(a1), jnp.asarray(cs),
                rts_lo, rts_hi,
            )
            count = int(sp.fence(total))
            ts = np.asarray(sp.fence(top_ts))
            cols = np.asarray(sp.fence(top_cols))
        if profile is not None:
            profile.device_acc_s += time.perf_counter() - tdev
        valid = ts != int(INVALID_TS)
        return count, keypack.unrev_ts(ts[valid]), cols[valid]

    # -------------------------------------------------------- index path
    def _index_step(self, prog: FilterProgram, n_conds: int, combine: str,
                    d: DistStore):
        from ..kernels.filter_scan.ops import pad_program

        opc, a0, a1, cs = pad_program(prog)
        step = self._cached_step(
            ("index", n_conds, combine, len(opc), cs.shape, d.has_runs),
            lambda: build_index_step(
                d.mesh, n_conds, combine, len(opc), cs.shape,
                self.top_k, self.index_postings, self.index_rows,
                runs=d.has_runs,
            ),
        )
        return step, (opc, a0, a1, cs)

    def _cond_ranges(self, plan: QueryPlan, t0: int, t1: int):
        """Per-condition packed index-key [lo, hi) ranges for the batch's
        time window (lo == hi for never-seen values: empty posting range)."""
        rts_lo = keypack.rev_ts(t1)
        rts_hi = keypack.rev_ts(t0)
        k = len(plan.index_conds)
        lo = np.zeros(k, np.int64)
        hi = np.zeros(k, np.int64)
        for i, c in enumerate(plan.index_conds):
            code = self.store.dictionaries[c.field].lookup(c.value)
            if code is None:
                continue
            fid = self.store.schema.field_id(c.field)
            lo[i] = keypack.pack_index_key(fid, code, rts_lo)
            hi[i] = keypack.pack_index_key(fid, code, rts_hi) + 1
        return lo, hi

    def _index_args(self, d: DistStore):
        args = (d.rev_ts, d.cols, d.ix_keys)
        if d.has_runs:
            args += self._ev_levels(d) + self._ix_levels(d)
        return args

    # reprolint: hot-path — the per-batch device program of the index schemes
    def scan_index_range(self, plan: QueryPlan, tree, t0: int, t1: int,
                         dist: Optional[DistStore] = None, profile=None):
        """One index-mode range across all tablets (paper Fig 2 on-mesh):
        postings lookup per condition per level, device-side
        intersect/union, candidate-row fetch from every level, and the
        FULL tree re-checked on candidates.
        Returns (global_count, top-k (ts, cols), truncated, candidates);
        `truncated` > 0 means a posting/row slab overflowed and the count
        is a lower bound — the executor falls back to filter-scan then."""
        d = dist if dist is not None else self._sync()
        if d.groups is not None:
            # Composite snapshot: postings of one (field, value) live in
            # whichever groups' tablets hold matching rows — every group
            # is searched, partial counts/truncation/candidates sum.
            total = n_trunc = n_cands = 0
            ts_parts, col_parts = [], []
            for sub in d.groups:
                c, ts, cols, tr, ca = self.scan_index_range(
                    plan, tree, t0, t1, dist=sub, profile=profile
                )
                total += c
                n_trunc += tr
                n_cands += ca
                ts_parts.append(ts)
                col_parts.append(cols)
            return (
                total, np.concatenate(ts_parts), np.concatenate(col_parts),
                n_trunc, n_cands,
            )
        prog = compile_tree(self.store, tree)
        step, (opc, a0, a1, cs) = self._index_step(
            prog, len(plan.index_conds), plan.combine, d
        )
        lo, hi = self._cond_ranges(plan, t0, t1)
        # Span + fenced materialization (this path had NEITHER: its
        # device wait was invisible to tracing and charged to the caller
        # as host time — found by reprolint's no-sync-in-hot-path rule).
        tdev = time.perf_counter()
        with span("query.scan_index_range", cat="query") as sp:
            total, top_ts, top_cols, truncated, cands = step(
                *self._index_args(d),
                jnp.asarray(opc), jnp.asarray(a0), jnp.asarray(a1), jnp.asarray(cs),
                jnp.asarray(lo), jnp.asarray(hi),
            )
            count = int(sp.fence(total))
            ts = np.asarray(sp.fence(top_ts))
            cols = np.asarray(sp.fence(top_cols))
            n_trunc = int(sp.fence(truncated))
            n_cands = int(sp.fence(cands))
        if profile is not None:
            profile.device_acc_s += time.perf_counter() - tdev
        valid = ts != int(INVALID_TS)
        return (count, keypack.unrev_ts(ts[valid]), cols[valid], n_trunc, n_cands)

    # ---------------------------------------------------- planned execution
    # reprolint: hot-path
    def _exec_range(self, plan: QueryPlan, tree, t0: int, t1: int, stats=None,
                    dist: Optional[DistStore] = None, profile=None) -> DistBatch:
        d = dist if dist is not None else self.dist
        if plan.mode == "index" and d.has_index:
            count, ts, cols, truncated, cands = self.scan_index_range(
                plan, tree, t0, t1, dist=d, profile=profile
            )
            if stats is not None:
                stats.index_keys_scanned += cands
            if not truncated:
                return DistBatch(count, ts, cols)
            # Slab overflow: redo this range with the exact filter-scan
            # step (results identical, just without the candidate cap).
        count, ts, cols = self.scan_range(tree, t0, t1, dist=d, profile=profile)
        return DistBatch(count, ts, cols)

    def execute(
        self,
        tree,
        t_start: int,
        t_stop: int,
        use_index: bool = True,
        batched: bool = True,
        stats=None,
    ):
        """Stream DistBatch results for a planned query — the distributed
        QueryProcessor.execute. plan_query picks the access path from the
        mesh-resident densities (heuristics 1-4); index-mode plans run
        build_index_step per batch, filter plans the scan step; provably
        empty plans (zero-density intersect branch) never touch a device.
        Implemented over QueryRun: the whole query is pinned to one
        published snapshot."""
        run = QueryRun(
            self, tree, t_start, t_stop,
            use_index=use_index, batched=batched, stats=stats,
        )
        while not run.done:
            blk = run.step()
            if blk is not None:
                yield blk

    def run_scheme(self, scheme: str, t_start: int, t_stop: int, tree=None, **kw):
        """The paper's four experimental schemes by name, distributed —
        mirrors QueryProcessor.run_scheme."""
        flags = {
            "scan": dict(use_index=False, batched=False),
            "batched_scan": dict(use_index=False, batched=True),
            "index": dict(use_index=True, batched=False),
            "batched_index": dict(use_index=True, batched=True),
        }[scheme]
        return self.execute(tree, t_start, t_stop, **flags, **kw)

    def _agg_step(self, prog: FilterProgram, grouping: ResolvedGrouping,
                  d: DistStore):
        from ..kernels.filter_scan.ops import pad_program

        opc, a0, a1, cs = pad_program(prog)
        key = (
            "agg", len(opc), cs.shape, grouping.fids, grouping.strides,
            grouping.size, grouping.n_buckets, grouping.spec.time_bucket_s,
            grouping.spec.op, grouping.value_fid, d.has_runs,
        )
        step = self._cached_step(
            key,
            lambda: build_aggregate_step(
                d.mesh,
                grouping.fids,
                grouping.strides,
                grouping.size,
                grouping.n_buckets,
                grouping.spec.time_bucket_s,
                grouping.spec.op,
                grouping.value_fid,
                runs=d.has_runs,
            ),
        )
        return step, (opc, a0, a1, cs)

    def _index_agg_step(self, prog: FilterProgram, grouping: ResolvedGrouping,
                        n_conds: int, combine: str, d: DistStore):
        from ..kernels.filter_scan.ops import pad_program

        opc, a0, a1, cs = pad_program(prog)
        key = (
            "aggix", n_conds, combine, len(opc), cs.shape, grouping.fids,
            grouping.strides, grouping.size, grouping.spec.time_bucket_s,
            grouping.spec.op, grouping.value_fid, d.has_runs,
        )
        step = self._cached_step(
            key,
            lambda: build_index_aggregate_step(
                d.mesh, n_conds, combine, len(opc), cs.shape,
                grouping.fids, grouping.strides, grouping.size,
                grouping.spec.time_bucket_s, grouping.spec.op,
                grouping.value_fid, self.index_postings, self.index_rows,
                runs=d.has_runs,
            ),
        )
        return step, (opc, a0, a1, cs)

    @staticmethod
    def _materialize_agg(grouping: ResolvedGrouping, aggs, cnts) -> AggregateResult:
        """Host-side epilogue: only groups with >= 1 matching row exist."""
        aggs = np.asarray(aggs).astype(np.int64)
        cnts = np.asarray(cnts)
        live = cnts > 0
        gids = np.flatnonzero(live).astype(np.int64)
        return AggregateResult(grouping, gids, aggs[live], cnts[live])

    # reprolint: hot-path — one-shot aggregate turns run through here
    def aggregate_range(
        self, spec: AggregateSpec, tree, t0: int, t1: int,
        use_index: bool = True, stats=None, dist: Optional[DistStore] = None,
    ) -> AggregateResult:
        """Scan-time aggregation across all tablets in ONE device program —
        the distributed lowering of QueryProcessor.aggregate(), planner
        driven: selective trees (index-mode plans) aggregate over ONLY the
        gathered index candidates (build_index_aggregate_step), provably
        empty plans skip the device entirely, and everything else — or an
        overflowed candidate slab — runs the exact filter-scan
        aggregation. Returns the already-merged (psum'd) per-group
        result; only groups with at least one matching row materialize
        host-side. `dist` pins an already-published snapshot (serve-plane
        sessions); default syncs to the plane's latest."""
        d = dist if dist is not None else self._sync()
        grouping = resolve_grouping(self.store, spec, t0, t1)
        source = _PinnedSource(self, d) if d.has_index else self.store
        plan = plan_query(
            source, tree, t0, t1, w=self.w,
            use_index=use_index and d.has_index,
        )
        if stats is not None:
            stats.plan = plan
        if plan.mode == "empty":
            e = np.empty(0, np.int64)
            return AggregateResult(grouping, e, e.copy(), e.copy())
        prog = compile_tree(self.store, tree)
        vt = grouping.value_table
        if vt is None:
            vt = np.ones(1, np.int32)  # unused placeholder (count op)
        # One resolve + one plan serve every tablet group; a composite
        # snapshot runs the per-group executor per sub-store (each group
        # falls back to scan-agg INDEPENDENTLY on its own slab overflow)
        # and folds the dense per-group partials on device — rows are
        # disjoint across groups, so sum/count add and min/max fold
        # elementwise against their identities, cnts always add.
        subs = d.groups if d.groups is not None else (d,)
        aggs, cnts = self._agg_range_on(subs[0], plan, grouping, prog, vt, t0, t1, stats)
        op = grouping.spec.op
        for sub in subs[1:]:
            a, c = self._agg_range_on(sub, plan, grouping, prog, vt, t0, t1, stats)
            if op in ("count", "sum"):
                aggs = aggs + a
            elif op == "min":
                aggs = jnp.minimum(aggs, a)
            else:
                aggs = jnp.maximum(aggs, a)
            cnts = cnts + c
        return self._materialize_agg(grouping, aggs, cnts)

    # reprolint: hot-path — aggregate_range's per-group device executor
    def _agg_range_on(self, d: DistStore, plan: QueryPlan,
                      grouping: ResolvedGrouping, prog: FilterProgram,
                      vt, t0: int, t1: int, stats=None):
        """Run one (sub-)snapshot's aggregation and return the DENSE
        per-group (aggs, cnts) device arrays — the caller folds partials
        across tablet groups and materializes once."""
        if plan.mode == "index" and d.has_index:
            step, (opc, a0, a1, cs) = self._index_agg_step(
                prog, grouping, len(plan.index_conds), plan.combine, d
            )
            lo, hi = self._cond_ranges(plan, t0, t1)
            aggs, cnts, truncated, cands = step(
                *self._index_args(d),
                jnp.asarray(opc), jnp.asarray(a0), jnp.asarray(a1), jnp.asarray(cs),
                jnp.asarray(vt),
                jnp.asarray(lo), jnp.asarray(hi),
                jnp.int32(grouping.bucket_lo),
            )
            if stats is not None:
                stats.index_keys_scanned += int(cands)
            if not int(truncated):
                return aggs, cnts
            # Slab overflow: exact filter-scan aggregation below.
        step, (opc, a0, a1, cs) = self._agg_step(prog, grouping, d)
        args = (d.rev_ts, d.cols, d.counts)
        if d.has_runs:
            args += self._ev_levels(d)
        aggs, cnts = step(
            *args,
            jnp.asarray(opc), jnp.asarray(a0), jnp.asarray(a1), jnp.asarray(cs),
            jnp.asarray(vt),
            jnp.int32(keypack.rev_ts(t1)), jnp.int32(keypack.rev_ts(t0) + 1),
            jnp.int32(grouping.bucket_lo),
        )
        return aggs, cnts

    def execute_batched(self, tree, t_start: int, t_stop: int, stats=None):
        """Algorithm 2 over the distributed scan."""
        d = self._sync()
        batcher = AdaptiveBatcher(
            t_start=t_start, t_stop=t_stop, b0=self.store.rows_per_second() and 10.0 / self.store.rows_per_second()
        )
        results = []
        while not batcher.done:
            lo, hi = batcher.next_range()
            t0 = time.perf_counter()
            count, ts, cols = self.scan_range(tree, int(lo), int(hi), dist=d)
            batcher.update(time.perf_counter() - t0, count)
            results.append((count, ts, cols))
            if stats is not None:
                stats.batches += 1
                stats.rows += count
        return results
