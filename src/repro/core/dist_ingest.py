"""Distributed ingest plane — writable device-resident LSM tablets for
ALL THREE of the paper's tables.

The paper's headline experiment (§IV-A, Figs 3-4) is ingest scalability vs
client processes x tablet servers; until this module the mesh data plane
was read-only (dist_query scattered a finished host store post hoc). Here
every mesh device hosts `tablets_per_device` *writable* tablet servers,
and the full LSM lifecycle of core/tables.py runs as jitted shard_map
programs over device-resident state:

    append   DistBatchWriter shards encoded events by row hash; each
             tablet picks its rows out of the replicated batch and
             scatter-appends them into its memtable slab
    minor    per-tablet memtable sort into the next sorted-run slot
    major    k-way merge of runs + base (kernels/merge_runs: one
             stable sort, or the Pallas rank kernel when a caller asks
             for kernel_backend="pallas") —
             BLOCKING the writer that tripped it, which is the paper's
             backpressure, reproduced on the mesh

Each tablet owns three table FAMILIES, the paper's per-source schema
(§II, Fig 1) maintained in lockstep through the same programs:

    ev   event table      key = rev_ts (int32), payload = field codes
    ix   index table      key = field|value|rev_ts packed int64 — the
                          D4M-style transpose table; postings for one
                          (field, value) are a contiguous sorted rev_ts
                          range, which is what the distributed index
                          query path binary-searches
    ag   aggregate table  key = field|value|time_bucket packed int64,
                          payload = count (int64) — duplicate keys are
                          summed at major compaction (Accumulo's
                          combiner-on-compaction); the query planner
                          reads densities from it with a psum

Index and aggregate entries are SYNTHESIZED ON DEVICE inside the append
program from the event rows themselves (writers ship only events):
index maintenance rides the ingest path, never a post-hoc build — the
index is live at publish() with no rebuild, per the 100M-inserts/sec
study's design (arXiv:1406.4923).

PLANE SHARDING (per-tablet-group ownership): the plane is decomposed
into ``n_groups`` independent :class:`TabletGroup` shards. Each group
owns a CONTIGUOUS range of ``n_tablets / n_groups`` global tablets with
its OWN OwnedLock, device state, host fill/run mirrors, generation
tags, and fold-debt accounting — so W concurrent DistBatchWriters whose
row-hash shards land on disjoint groups append fully concurrently
instead of serializing behind one plane lock (the D4M 100M-inserts/sec
curve only climbs when client parallelism is not funneled through a
single coordination point). The jitted step programs are SHARED across
groups through one :class:`_PlanePrograms` cache (every group has
identical slab shapes, so one trace/compile serves all G shards).
:meth:`DistIngestPlane.publish` composes per-group zero-copy snapshots
into one DistStore (per-group gens under ``DistStore.gens``) without a
global stop-the-world: each group seals under only its own lock, and a
group untouched since its last seal ALIASES its previous snapshot.
``compact_step`` folds one increment of the MOST-INDEBTED group under
only that group's lock. With ``n_groups == 1`` (the default) the facade
degenerates to the former single-lock plane — same lock name, same
state dict, same publish identity/aliasing guarantees.

Per-tablet device counters (rows, minor/major compactions, per-family
overflow) record the blocked-writer dynamics; host wall-clock blocked
seconds accrue PER WRITER (each writer's own tripped-major drains), with
the plane scalar kept as their sum — the paper's §IV-A per-client
backpressure curve is directly plottable from telemetry(). Exact host
mirrors of the per-tablet rows/minor/major counters are also snapshot
into ``plane{n}`` registry gauges at publish()/telemetry() boundaries —
zero device syncs, the mirrors are maintained in lockstep with the
device programs.

publish() is a SNAPSHOT, not a fold: it seals the memtables (one
fill-bounded sort, O(live fill) — the host fill mirror picks the slab
head to sort, pow2-bucketed) and hands out a DistStore view of ALL
levels — base, run slabs, sealed memtable — for every family. The
distributed read path (core/dist_query.py) searches every level, so
freshly ingested rows AND their index/aggregate entries become visible
to DistQueryProcessor without a host round trip, a re-scatter, or the
former O(capacity) run->base re-merge per freshness flip. Major
compaction (threshold-driven during ingest, or batched in the
background via compact()) is the ONLY fold point.

Host-side flush triggers are exact with zero device syncs: tablet
assignments are computed host-side, so a bincount per chunk mirrors the
device memtable fills and run-slot counts precisely — compactions fire
only when some tablet is actually full. Index/aggregate slabs are sized
n_indexed x the event slabs, so one mirror covers all three families
(each event contributes exactly n_indexed entries to each).
"""
from __future__ import annotations

import itertools
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import keypack
from .dist_query import DistStore
from .ingest import BatchWriter, IngestMetrics, check_shard_guidance
from .store import DEFAULT_AGG_BUCKET_SECONDS
from ..kernels.merge_runs.ops import _pow2, sort_with_payload
from ..obs import MetricsRegistry, OwnedLock, span

REV_PAD = np.iinfo(np.int32).max  # +inf rev_ts sentinel (matches DistStore)
KEY_PAD64 = np.iinfo(np.int64).max  # +inf packed-key sentinel (ix/ag)

_plane_seq = itertools.count()  # names each plane's private metrics registry


def _n_devices(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def _linear_device_index(mesh: Mesh):
    """Row-major device index over the mesh axes — the shard_map slab of a
    P(axes, ...)-sharded array on this device covers tablets
    [idx * tablets_per_device, (idx + 1) * tablets_per_device)."""
    idx = jnp.int32(0)
    for a in mesh.axis_names:
        idx = idx * jnp.int32(mesh.shape[a]) + lax.axis_index(a)
    return idx


@dataclass(frozen=True)
class _Family:
    """One table family's static shape parameters. Every family shares the
    tablet grid, run-slot count and compaction lifecycle; they differ in
    key dtype, payload width, slab sizes, and whether duplicate keys are
    combined (summed) at major compaction."""

    name: str
    key_dtype: np.dtype
    sentinel: int
    width: int
    col_dtype: np.dtype
    mem_rows: int
    capacity: int
    combine: str = "none"  # major-scope fold: "none" | "sum" | "dedup"


def _combine_dup_keys(keys, vals, sentinel):
    """Sum payloads of equal adjacent keys in a sorted (sentinel-tailed)
    sequence and compact the unique keys to the front — the traceable form
    of tables.py::_combine_sorted, used for the aggregate family's
    combiner-on-compaction (and, with vals ignored, the index family's
    dedup). Returns (ukeys, usums, n_unique); usums is zero past n_unique.

    Gather- and scatter-free (a TPU runs both far slower than a sort at
    tablet sizes): the last entry of each run of equal keys keeps its key
    and the running sum of vals, every other entry becomes the sentinel,
    and one stable sort moves the survivors to the front in key order —
    each segment's sum is then the difference of neighbouring running
    sums."""
    n = keys.shape[0]
    is_tail = jnp.concatenate([keys[1:] != keys[:-1], jnp.ones((1,), bool)])
    keep = is_tail & (keys != sentinel)
    n_unique = keep.sum(dtype=jnp.int32)
    running = jnp.cumsum(vals.astype(jnp.int64))
    ukeys, urun = lax.sort(
        (jnp.where(keep, keys, sentinel), running), num_keys=1, is_stable=True
    )
    sums = urun - jnp.concatenate([jnp.zeros((1,), jnp.int64), urun[:-1]])
    sums = jnp.where(jnp.arange(n) < n_unique, sums, 0)
    return ukeys, sums, n_unique


def _sort_masked(keys, cols, n, sentinel):
    """Mask entries past the fill to the sentinel and sort (payload travels
    with its key) — memtable slots beyond n hold stale rows left over from
    before the last flush. Shared by minor compaction and the publish seal
    so both produce the same sorted, sentinel-tailed level layout."""
    valid = jnp.arange(keys.shape[0], dtype=jnp.int32) < n
    return sort_with_payload(jnp.where(valid, keys, sentinel), cols)


class _PlanePrograms:
    """The plane's static configuration + ONE shared cache of jitted step
    programs (append / minor / major / fold_one / seal variants).

    Every :class:`TabletGroup` of a plane has identical slab shapes (same
    tablets-per-device-per-group, mem_rows, max_runs, families), so the
    shard_map programs are shape-identical across groups — caching them
    here means G shards pay ONE trace + compile per step, not G. The
    cache has its own small lock (never held while device programs run);
    lock order is always group.lock -> programs._lock, never reversed."""

    def __init__(
        self,
        mesh: Mesh,
        n_fields: int,
        capacity: int,
        tablets_per_device: int,
        mem_rows: int,
        max_runs: int,
        append_rows: int,
        indexed_fids: Tuple[int, ...],
        agg_bucket_s: int,
        kernel_backend: str,
    ):
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.n_fields = int(n_fields)
        # Per-GROUP tablets per device: a group's state arrays shard this
        # many tablets onto each mesh device.
        self.tablets_per_device = int(tablets_per_device)
        self.n_tablets = _n_devices(mesh) * self.tablets_per_device
        self.capacity = int(capacity)
        self.mem_rows = int(mem_rows)
        self.max_runs = int(max_runs)
        self.append_rows = int(min(append_rows, mem_rows))
        self.indexed_fids = tuple(int(f) for f in indexed_fids)
        self.agg_bucket_s = int(agg_bucket_s)
        self.kernel_backend = kernel_backend
        self.families: Tuple[_Family, ...] = self._make_families()
        self._steps: Dict[object, object] = {}  # guarded-by: _lock
        self._lock = OwnedLock("plane_step_lock")

    # ----------------------------------------------------------- families
    def _make_families(self) -> Tuple[_Family, ...]:
        fams = [
            _Family(
                "ev", np.dtype(np.int32), REV_PAD, self.n_fields,
                np.dtype(np.int32), self.mem_rows, self.capacity,
            )
        ]
        n_idx = len(self.indexed_fids)
        if n_idx:
            fams.append(
                _Family(
                    "ix", np.dtype(np.int64), KEY_PAD64, 0,
                    np.dtype(np.int32), n_idx * self.mem_rows, n_idx * self.capacity,
                    combine="dedup",
                )
            )
            fams.append(
                _Family(
                    "ag", np.dtype(np.int64), KEY_PAD64, 1,
                    np.dtype(np.int64), n_idx * self.mem_rows, n_idx * self.capacity,
                    combine="sum",
                )
            )
        return tuple(fams)

    # --------------------------------------------------------------- specs
    def _spec_of(self, name: str) -> P:
        ax = self.axes
        if name.endswith(("_mem_k", "_base_k")):
            return P(ax, None)
        if name.endswith(("_mem_c", "_base_c")):
            return P(ax, None, None)
        if name.endswith("_run_k"):
            return P(ax, None, None)
        if name.endswith("_run_c"):
            return P(ax, None, None, None)
        if name.endswith("_run_n"):
            return P(ax, None)
        return P(ax)  # *_mem_n, *_base_n, *_overflow, n_runs, rows, minor, major

    def _specs(self, names) -> Dict[str, P]:
        return {n: self._spec_of(n) for n in names}

    def _shardings(self, specs: Dict[str, P]) -> Dict[str, NamedSharding]:
        """Pinned output shardings: every program hands back its state
        with exactly the sharding the state was created with (jit would
        otherwise canonicalise some, e.g. to P() on a one-device mesh),
        so a step's next call hits the executable it already compiled."""
        return {n: NamedSharding(self.mesh, sp) for n, sp in specs.items()}

    def state_layout(self) -> Dict[str, Tuple[Tuple[int, ...], np.dtype, int]]:
        """(shape, dtype, fill) of every per-group state array: what a
        TabletGroup allocates, and the shapes an ahead-of-time compile
        for a described chip hands the step programs (abstract_state)."""
        t, k = self.n_tablets, self.max_runs
        i32, i64 = np.dtype(np.int32), np.dtype(np.int64)
        out = {
            "n_runs": ((t,), i32, 0),
            "rows": ((t,), i64, 0),
            "minor": ((t,), i32, 0),
            "major": ((t,), i32, 0),
        }
        for f in self.families:
            p, m, c = f.name, f.mem_rows, f.capacity
            out[f"{p}_mem_k"] = ((t, m), f.key_dtype, 0)
            out[f"{p}_mem_c"] = ((t, m, f.width), f.col_dtype, 0)
            out[f"{p}_mem_n"] = ((t,), i32, 0)
            out[f"{p}_run_k"] = ((t, k, m), f.key_dtype, f.sentinel)
            out[f"{p}_run_c"] = ((t, k, m, f.width), f.col_dtype, 0)
            out[f"{p}_run_n"] = ((t, k), i32, 0)
            out[f"{p}_base_k"] = ((t, c), f.key_dtype, f.sentinel)
            out[f"{p}_base_c"] = ((t, c, f.width), f.col_dtype, 0)
            out[f"{p}_base_n"] = ((t,), i32, 0)
            out[f"{p}_overflow"] = ((t,), i32, 0)
        return out

    def abstract_state(self, names) -> Dict[str, jax.ShapeDtypeStruct]:
        """Shapes + shardings (no allocation) of the named state arrays."""
        layout = self.state_layout()
        return {
            n: jax.ShapeDtypeStruct(
                layout[n][0], layout[n][1],
                sharding=NamedSharding(self.mesh, self._spec_of(n)),
            )
            for n in names
        }

    # --------------------------------------------------------- name lists
    def _append_names(self):
        names = ["rows"]
        for f in self.families:
            p = f.name
            names += [f"{p}_mem_k", f"{p}_mem_c", f"{p}_mem_n", f"{p}_overflow"]
        return names

    def _minor_names(self):
        names = ["n_runs", "minor"]
        for f in self.families:
            p = f.name
            names += [
                f"{p}_mem_k", f"{p}_mem_c", f"{p}_mem_n",
                f"{p}_run_k", f"{p}_run_c", f"{p}_run_n",
            ]
        return names

    def _major_names(self):
        run = ["n_runs", "major"]
        base = []
        for f in self.families:
            p = f.name
            run += [f"{p}_run_k", f"{p}_run_c", f"{p}_run_n", f"{p}_overflow"]
            base += [f"{p}_base_k", f"{p}_base_c", f"{p}_base_n"]
        return run, base

    def _seal_names(self):
        names = []
        for f in self.families:
            p = f.name
            names += [f"{p}_mem_k", f"{p}_mem_c", f"{p}_mem_n"]
        return names

    def seal_buckets(self) -> Tuple[int, ...]:
        """Every seal_rows value _seal_bucket can return: 8, 16, ... up to
        mem_rows (the last possibly not a power of two)."""
        out, rows = [], 8
        while True:
            out.append(min(rows, self.mem_rows))
            if rows >= self.mem_rows:
                return tuple(out)
            rows *= 2

    def _seal_bucket(self, fill_max: int) -> int:
        """Event-family slot count the seal program must sort to cover a
        memtable fill of fill_max — the live fill rounded up to a power of
        two (floored at 8) so the number of distinct seal compilations is
        log2-bounded, clamped to the slab capacity."""
        return int(min(max(_pow2(max(fill_max, 1)), 8), self.mem_rows))

    # ----------------------------------------------------------- step cache
    def _get_step(self, key, build):
        """Shared compile cache: two groups' (or two writers') first
        flushes racing here must trace once, not twice — the cache lock
        serializes build + insert (the former in-plane guarded dict,
        found by reprolint's guarded-by rule)."""
        with self._lock.hold("step_build"):
            if key not in self._steps:
                self._steps[key] = build()
            return self._steps[key]

    def append_step(self):
        return self._get_step("append", self._build_append)

    def minor_step(self):
        return self._get_step("minor", self._build_minor)

    def major_step(self):
        return self._get_step("major", self._build_major)

    def fold_one_step(self):
        return self._get_step("fold_one", self._build_fold_one)

    def seal_step(self, seal_rows: int):
        return self._get_step(
            ("seal", seal_rows), lambda: self._build_seal(seal_rows)
        )

    def precompile(self) -> None:
        """Compile the minor, fold_one and major programs and every seal
        bucket CONCURRENTLY, ahead of their first call. XLA compiles
        outside the GIL and a jitted function's later call with the same
        shapes and shardings reuses the executable, so startup pays about
        the slowest compile instead of the sum (on a v5e each seal bucket
        and each fold program is a tens-of-seconds compile)."""
        run_names, base_names = self._major_names()
        folds = (self.abstract_state(run_names), self.abstract_state(base_names))
        jobs = [
            (self.minor_step(), (self.abstract_state(self._minor_names()),)),
            (self.fold_one_step(), folds),
            (self.major_step(), folds),
        ] + [
            (self.seal_step(r), (self.abstract_state(self._seal_names()),))
            for r in self.seal_buckets()
        ]
        with ThreadPoolExecutor(len(jobs)) as pool:
            done = [pool.submit(lambda s, a: s.lower(*a).compile(), s, a) for s, a in jobs]
            for f in done:
                f.result()

    # --------------------------------------------------------- step builders
    def _build_append(self):
        mesh, tl = self.mesh, self.tablets_per_device
        families = self.families
        fids = self.indexed_fids
        bucket_s = self.agg_bucket_s
        names = self._append_names()

        def scatter_append(mem_k, mem_c, n, keys, cols, mask):
            """Scatter-append masked entries: dest = running fill; foreign
            and overflow entries map out of bounds and drop."""
            m = mem_k.shape[0]
            dest = jnp.where(
                mask, n + jnp.cumsum(mask.astype(jnp.int32)) - 1, jnp.int32(m)
            )
            mem_k = mem_k.at[dest].set(keys, mode="drop")
            mem_c = mem_c.at[dest].set(cols, mode="drop")
            want = n + mask.sum(dtype=jnp.int32)
            new_n = jnp.minimum(want, jnp.int32(m))
            return mem_k, mem_c, new_n, new_n - n, want - new_n

        def device_fn(st, b_rts, b_cols, b_tab):
            dev = _linear_device_index(mesh)
            # Index/aggregate entries synthesized from the event rows —
            # index maintenance rides the ingest path (module docstring).
            if fids:
                rts64 = b_rts.astype(jnp.int64)
                ts64 = jnp.int64(keypack.TS_MAX) - rts64
                bucket = ts64 // jnp.int64(bucket_s)
                # Traceable twins of keypack.pack_index_key/pack_agg_key
                # (those are numpy; the bit layout constants are shared).
                ix_f = keypack.VALUE_BITS + keypack.TS_BITS
                ag_f = keypack.VALUE_BITS + keypack.BUCKET_BITS
                ik_parts, ak_parts = [], []
                for fid in fids:
                    code = b_cols[:, fid].astype(jnp.int64)
                    ik_parts.append(
                        (jnp.int64(fid) << ix_f) | (code << keypack.TS_BITS) | rts64
                    )
                    ak_parts.append(
                        (jnp.int64(fid) << ag_f) | (code << keypack.BUCKET_BITS) | bucket
                    )
                ikeys = jnp.concatenate(ik_parts)
                akeys = jnp.concatenate(ak_parts)
                icols = jnp.zeros((ikeys.shape[0], 0), jnp.int32)
                acols = jnp.ones((akeys.shape[0], 1), jnp.int64)

            def one(i, loc):
                gid = dev * jnp.int32(tl) + i
                mine = b_tab == gid
                out = dict(loc)
                entries = {"ev": (b_rts, b_cols, mine)}
                if fids:
                    mine_t = jnp.tile(mine, len(fids))
                    entries["ix"] = (ikeys, icols, mine_t)
                    entries["ag"] = (akeys, acols, mine_t)
                for f in families:
                    p = f.name
                    keys, cols, mask = entries[p]
                    mem_k, mem_c, new_n, appended, lost = scatter_append(
                        loc[f"{p}_mem_k"], loc[f"{p}_mem_c"], loc[f"{p}_mem_n"],
                        keys, cols, mask,
                    )
                    out[f"{p}_mem_k"] = mem_k
                    out[f"{p}_mem_c"] = mem_c
                    out[f"{p}_mem_n"] = new_n
                    out[f"{p}_overflow"] = loc[f"{p}_overflow"] + lost
                    if p == "ev":
                        out["rows"] = loc["rows"] + appended.astype(loc["rows"].dtype)
                return out

            idx = jnp.arange(tl, dtype=jnp.int32)
            return jax.vmap(one, in_axes=(0, 0))(idx, st)

        smapped = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(self._specs(names), P(None), P(None, None), P(None)),
            out_specs=self._specs(names),
            check_vma=False,
        )
        # The ONE allowed donation in the planes: the append step donates
        # only the live memtable slabs, which publish() never aliases — a
        # snapshot seals a sorted COPY of the memtable (_sort_level), so
        # no published DistStore can see the donated buffers.
        out = self._shardings(self._specs(names))
        return jax.jit(smapped, donate_argnums=(0,), out_shardings=out)  # reprolint: disable=no-donate-in-plane

    def _build_minor(self):
        mesh, k = self.mesh, self.max_runs
        families = self.families
        names = self._minor_names()

        def device_fn(st):
            def one(loc):
                nr = loc["n_runs"]
                # All families flush in lockstep: a tablet holds event rows
                # iff it holds index/aggregate entries for them.
                do = (loc["ev_mem_n"] > 0) & (nr < jnp.int32(k))
                slot = jnp.clip(nr, 0, k - 1)
                out = dict(loc)
                for f in families:
                    p = f.name
                    n = loc[f"{p}_mem_n"]
                    skeys, scols = _sort_masked(
                        loc[f"{p}_mem_k"], loc[f"{p}_mem_c"], n, f.sentinel
                    )
                    rk, rc, rn = loc[f"{p}_run_k"], loc[f"{p}_run_c"], loc[f"{p}_run_n"]
                    out[f"{p}_run_k"] = rk.at[slot].set(jnp.where(do, skeys, rk[slot]))
                    out[f"{p}_run_c"] = rc.at[slot].set(jnp.where(do, scols, rc[slot]))
                    out[f"{p}_run_n"] = rn.at[slot].set(jnp.where(do, n, rn[slot]))
                    out[f"{p}_mem_n"] = jnp.where(do, 0, n)
                out["n_runs"] = nr + do.astype(nr.dtype)
                out["minor"] = loc["minor"] + do.astype(jnp.int32)
                return out

            return jax.vmap(one)(st)

        smapped = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(self._specs(names),),
            out_specs=self._specs(names),
            check_vma=False,
        )
        # NOT donated: publish() hands out DistStore views of the run
        # slabs (run-aware reads), and on backends that implement donation
        # a donated minor would delete arrays a caller may still hold.
        return jax.jit(smapped, out_shardings=self._shardings(self._specs(names)))

    def _build_major(self):
        from ..kernels.merge_runs import merge_base_runs_device

        mesh, k = self.mesh, self.max_runs
        families = self.families
        backend = self.kernel_backend
        run_names, base_names = self._major_names()

        def device_fn(rst, bst):
            def one(rloc, bloc):
                nr = rloc["n_runs"]
                do = nr > 0
                out_r = dict(rloc)
                out_b = {}
                for f in families:
                    p, m, c = f.name, f.mem_rows, f.capacity
                    # Base + K runs (m rows each) in one merge: the base
                    # wins ties, then earlier runs.
                    rn = rloc[f"{p}_run_n"]
                    bk, bc, bn = bloc[f"{p}_base_k"], bloc[f"{p}_base_c"], bloc[f"{p}_base_n"]
                    # Mask stale slots/rows (run_n is authoritative; slots
                    # past n_runs were zeroed at the previous major).
                    within = jnp.arange(m, dtype=jnp.int32)[None, :] < rn[:, None]
                    ck = jnp.where(within, rloc[f"{p}_run_k"], f.sentinel)
                    cc = jnp.where(within[..., None], rloc[f"{p}_run_c"], 0)
                    fk, fc = merge_base_runs_device(bk, bc, ck, cc, backend=backend)
                    if f.combine == "sum":
                        # Aggregate family: sum duplicate (field, value,
                        # bucket) keys — Accumulo's combiner at compaction
                        # scope. The base stays at unique-key cardinality.
                        fk, sums, total = _combine_dup_keys(fk, fc[:, 0], f.sentinel)
                        fc = sums[:, None].astype(fc.dtype)
                    elif f.combine == "dedup":
                        # Index family: repeated field|value|rev_ts keys
                        # collapse (the same key compaction, payload
                        # discarded — ix rows are zero-width) — without
                        # this the ix base accumulates duplicate postings
                        # forever. Exactness holds because the row fetch
                        # expands a candidate rev_ts by binary search over
                        # the event levels: ONE posting finds EVERY
                        # matching row.
                        fk, _, total = _combine_dup_keys(
                            fk, jnp.zeros(fk.shape, jnp.int32), f.sentinel
                        )
                    else:
                        total = bn + rn.sum(dtype=jnp.int32)
                    new_bn = jnp.where(do, jnp.minimum(total, jnp.int32(c)), bn)
                    lost = jnp.where(do, total - jnp.minimum(total, jnp.int32(c)), 0)
                    out_b[f"{p}_base_k"] = jnp.where(do, fk[:c], bk)
                    out_b[f"{p}_base_c"] = jnp.where(do, fc[:c], bc)
                    out_b[f"{p}_base_n"] = new_bn
                    out_r[f"{p}_run_n"] = jnp.where(do, jnp.zeros_like(rn), rn)
                    out_r[f"{p}_overflow"] = rloc[f"{p}_overflow"] + lost
                out_r["n_runs"] = jnp.where(do, 0, nr)
                out_r["major"] = rloc["major"] + do.astype(jnp.int32)
                return out_r, out_b

            return jax.vmap(one)(rst, bst)

        smapped = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(self._specs(run_names), self._specs(base_names)),
            out_specs=(self._specs(run_names), self._specs(base_names)),
            check_vma=False,
        )
        # Deliberately NOT donated (neither runs nor bases): publish()
        # hands out DistStore views of run slabs AND base runs, and on
        # backends that implement donation (TPU/GPU) a donated major
        # would delete arrays a caller may still hold. Majors are rare;
        # one copy each is the price of stable published views.
        return jax.jit(smapped, out_shardings=(
            self._shardings(self._specs(run_names)), self._shardings(self._specs(base_names))
        ))

    def _build_fold_one(self):
        """One INCREMENT of major compaction: every tablet folds its TOP
        run slot (n_runs - 1) into its base — one bounded 2-way merge of
        O(capacity + mem_rows) rows per family via the resumable
        merge_pair_device entry point, instead of the all-runs k-way
        fold. Folding the top slot keeps the remaining slots a contiguous
        [0, n_runs) prefix, so ANY prefix of increments leaves the exact
        LSM invariants every read primitive in dist_query.py already
        handles (sorted levels, live counts authoritative, combine folded
        at the base): an interrupted major is just a database with fewer
        runs. Fold order across slots only permutes equal keys — the
        per-key combines (sum / dedup) are commutative and event rows
        with equal rev_ts are order-free for every query primitive — so
        K increments agree with one compact() as a multiset (asserted
        against the numpy oracle in tests)."""
        from ..kernels.merge_runs import merge_pair_device

        mesh = self.mesh
        families = self.families
        backend = self.kernel_backend
        run_names, base_names = self._major_names()

        def device_fn(rst, bst):
            def one(rloc, bloc):
                nr = rloc["n_runs"]
                do = nr > 0
                slot = jnp.maximum(nr - 1, 0)
                out_r = dict(rloc)
                out_b = {}
                for f in families:
                    p, m, c = f.name, f.mem_rows, f.capacity
                    rn_slot = rloc[f"{p}_run_n"][slot]
                    # Mask stale rows past the slot's live count (slots
                    # hold leftovers from before earlier folds).
                    within = jnp.arange(m, dtype=jnp.int32) < rn_slot
                    ck = jnp.where(within, rloc[f"{p}_run_k"][slot], f.sentinel)
                    cc = jnp.where(within[:, None], rloc[f"{p}_run_c"][slot], 0)
                    bk, bc, bn = (
                        bloc[f"{p}_base_k"], bloc[f"{p}_base_c"], bloc[f"{p}_base_n"]
                    )
                    fk, fc = merge_pair_device(bk, bc, ck, cc, backend=backend)
                    if f.combine == "sum":
                        fk, sums, total = _combine_dup_keys(fk, fc[:, 0], f.sentinel)
                        fc = sums[:, None].astype(fc.dtype)
                    elif f.combine == "dedup":
                        fk, _, total = _combine_dup_keys(
                            fk, jnp.zeros(fk.shape, jnp.int32), f.sentinel
                        )
                    else:
                        total = bn + rn_slot
                    new_bn = jnp.where(do, jnp.minimum(total, jnp.int32(c)), bn)
                    lost = jnp.where(do, total - jnp.minimum(total, jnp.int32(c)), 0)
                    out_b[f"{p}_base_k"] = jnp.where(do, fk[:c], bk)
                    out_b[f"{p}_base_c"] = jnp.where(do, fc[:c], bc)
                    out_b[f"{p}_base_n"] = new_bn
                    out_r[f"{p}_run_n"] = rloc[f"{p}_run_n"].at[slot].set(
                        jnp.where(do, 0, rn_slot)
                    )
                    out_r[f"{p}_overflow"] = rloc[f"{p}_overflow"] + lost
                out_r["n_runs"] = nr - do.astype(nr.dtype)
                # The increment that folds the LAST run completes one
                # major — the per-tablet counter keeps its meaning
                # (number of run->base folds brought to empty).
                out_r["major"] = rloc["major"] + (do & (nr == 1)).astype(jnp.int32)
                return out_r, out_b

            return jax.vmap(one)(rst, bst)

        smapped = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(self._specs(run_names), self._specs(base_names)),
            out_specs=(self._specs(run_names), self._specs(base_names)),
            check_vma=False,
        )
        # NOT donated, same as the full major: published views alias the
        # run/base buffers and must survive the fold.
        return jax.jit(smapped, out_shardings=(
            self._shardings(self._specs(run_names)), self._shardings(self._specs(base_names))
        ))

    def _build_seal(self, seal_rows: int):
        """FILL-BOUNDED sorted snapshot of the memtables — the only
        per-publish device work. Only the first `seal_rows` slots of each
        event memtable (scaled per family: ix/ag slabs are n_indexed x
        wider) are sorted — O(fill log fill), not O(mem_rows log
        mem_rows): a publish right after a flush or a compact() pays for
        the handful of live rows, not the slab capacity. The sealed
        OUTPUT keeps the full (T, mem_rows) shape — sorted head +
        sentinel tail — so published DistStore level shapes never change
        and the compiled read programs never re-trace. Reads the live
        memtable slabs (no donation) and writes fresh sealed arrays, so
        later appends can't tear a published view."""
        mesh = self.mesh
        families = self.families
        names = self._seal_names()
        # Per-family head length: ix/ag fills are exactly n_indexed x the
        # event fill (one entry per indexed field per event).
        heads = {
            f.name: int(min(seal_rows * (f.mem_rows // self.mem_rows), f.mem_rows))
            for f in families
        }
        out_specs = {}
        for f in families:
            p = f.name
            out_specs[f"{p}_sealed_k"] = P(self.axes, None)
            out_specs[f"{p}_sealed_c"] = P(self.axes, None, None)
            out_specs[f"{p}_sealed_n"] = P(self.axes)

        def device_fn(st):
            def one(loc):
                out = {}
                for f in families:
                    p, m, h = f.name, f.mem_rows, heads[f.name]
                    n = loc[f"{p}_mem_n"]
                    # Same mask-past-fill + sort as a minor flush — over
                    # the live head only (publish() guarantees n <= h);
                    # the sentinel tail keeps the sealed level's sorted +
                    # sentinel-tailed invariant at full slab shape.
                    head_k, head_c = _sort_masked(
                        loc[f"{p}_mem_k"][:h], loc[f"{p}_mem_c"][:h], n, f.sentinel
                    )
                    out[f"{p}_sealed_k"] = jnp.concatenate(
                        [head_k, jnp.full((m - h,), f.sentinel, head_k.dtype)]
                    )
                    out[f"{p}_sealed_c"] = jnp.concatenate(
                        [head_c, jnp.zeros((m - h, f.width), head_c.dtype)]
                    )
                    out[f"{p}_sealed_n"] = n
                return out

            return jax.vmap(one)(st)

        smapped = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(self._specs(names),),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(smapped, out_shardings=self._shardings(out_specs))


class TabletGroup:
    """One shard of the ingest plane: a contiguous range of
    ``programs.n_tablets`` global tablets with its OWN lock, device
    state, host mirrors, generation tags and fold-debt accounting.

    A group is the former whole-plane DistIngestPlane body with the
    plane-global bits factored out: step programs come from the shared
    :class:`_PlanePrograms` cache (identical shapes across groups — one
    compile serves all), and counters land on the plane's shared metrics
    registry (per-writer blocked cells therefore still sum to the plane
    scalar no matter how waits split across groups). Everything below is
    guarded by ``self.lock`` — writers on DIFFERENT groups never contend.

    Global tablet ``t`` belongs to group ``t // n_tablets`` and is this
    group's local tablet ``t - t0``; all arrays here index local ids."""

    def __init__(
        self,
        gid: int,
        n_groups: int,
        programs: _PlanePrograms,
        m_seal,
        m_blocked,
        m_folds,
        m_last_seal_rows,
        m_group_stall=None,
        m_group_stall_events=None,
    ):
        self.gid = int(gid)
        self.programs = programs
        self.mesh = programs.mesh
        self.n_tablets = programs.n_tablets  # local (per-group) count
        self.t0 = self.gid * self.n_tablets  # global id of local tablet 0
        self._m_seal = m_seal
        self._m_blocked = m_blocked
        self._m_folds = m_folds
        self._m_last_seal_rows = m_last_seal_rows
        self._m_group_stall = m_group_stall
        self._m_group_stall_events = m_group_stall_events
        # The single-group plane keeps the historic lock name (occupancy
        # reports, benches and CI key on "plane_lock"); sharded planes
        # name each group's lock so the books attribute contention to the
        # group that serialized it.
        name = "plane_lock" if n_groups == 1 else f"plane_lock_g{self.gid}"
        self.lock = OwnedLock(name)
        # Exact host-side mirrors of the device memtable fills, run-slot
        # counts and per-tablet counters (see module docstring) — updated
        # in lockstep with the device programs' own guards, never read
        # back from the device. One fill mirror serves all families:
        # ix/ag fills are exactly n_indexed x the event fill per tablet.
        self._fill = np.zeros(self.n_tablets, np.int64)  # guarded-by: lock
        self._runs_host = np.zeros(self.n_tablets, np.int32)  # guarded-by: lock
        self._rows_host = np.zeros(self.n_tablets, np.int64)  # guarded-by: lock
        self._minor_host = np.zeros(self.n_tablets, np.int32)  # guarded-by: lock
        self._major_host = np.zeros(self.n_tablets, np.int32)  # guarded-by: lock
        self._dirty = True  # guarded-by: lock
        self._published: Optional[DistStore] = None  # guarded-by: lock
        # Generation tag per LSM level (shared by all families — they move
        # in lockstep): appends bump "mem"; a minor flush bumps "mem" +
        # "runs"; any fold into the base (full major or one compact_step
        # increment) bumps "runs" + "base". snapshot() keys its sealed-
        # memtable cache on the "mem" generation, so a publish after a
        # fold-only increment ALIASES the previous sealed arrays instead
        # of re-running the seal sort — snapshots never pay per-increment
        # device work for levels the increment didn't touch.
        self._gen: Dict[str, int] = {"mem": 0, "runs": 0, "base": 0}  # guarded-by: lock
        # (mem generation, sealed arrays, seal_rows) of the last seal run.
        self._sealed_cache: Optional[Tuple[int, Dict[str, jax.Array], int]] = None  # guarded-by: lock
        self.state = self._init_state()  # guarded-by: lock

    def _init_state(self) -> Dict[str, jax.Array]:
        pr = self.programs
        return {
            name: jax.device_put(
                np.full(shape, fill, dtype), NamedSharding(self.mesh, pr._spec_of(name))
            )
            for name, (shape, dtype, fill) in pr.state_layout().items()
        }

    def _sub(self, names) -> Dict[str, jax.Array]:  # holds: lock
        return {n: self.state[n] for n in names}

    # --------------------------------------------------------- compaction
    def _run_minor(self) -> None:  # holds: lock
        pr = self.programs
        step = pr.minor_step()
        self.state.update(step(self._sub(pr._minor_names())))
        # Mirror the device guard exactly: a tablet flushes iff it holds
        # rows AND has a free run slot.
        flushed = (self._fill > 0) & (self._runs_host < pr.max_runs)
        self._runs_host += flushed
        self._minor_host += flushed
        self._fill = np.where(flushed, 0, self._fill)
        if flushed.any():
            self._gen["mem"] += 1  # memtables drained
            self._gen["runs"] += 1  # run slabs gained a slot

    def _run_major(self) -> None:  # holds: lock
        pr = self.programs
        step = pr.major_step()
        run_names, base_names = pr._major_names()
        out_r, out_b = step(self._sub(run_names), self._sub(base_names))
        self.state.update(out_r)
        self.state.update(out_b)
        self._major_host += self._runs_host > 0
        if self._runs_host.max() > 0:
            self._gen["runs"] += 1
            self._gen["base"] += 1
        self._runs_host[:] = 0

    def _run_fold_one(self) -> None:  # holds: lock
        """One increment: every tablet with runs folds its top run slot
        into its base (see _build_fold_one). Host run mirror drops by one
        where it was positive — exactly the device guard."""
        pr = self.programs
        step = pr.fold_one_step()
        run_names, base_names = pr._major_names()
        out_r, out_b = step(self._sub(run_names), self._sub(base_names))
        self.state.update(out_r)
        self.state.update(out_b)
        # The increment that folds a tablet's LAST run completes a major.
        self._major_host += self._runs_host == 1
        if self._runs_host.max() > 0:
            self._gen["runs"] += 1
            self._gen["base"] += 1
        self._runs_host = np.maximum(self._runs_host - 1, 0).astype(self._runs_host.dtype)

    # ------------------------------------------------------------- ingest
    def ingest(
        self, rts: np.ndarray, cols: np.ndarray, tab: np.ndarray, writer_id: int = 0
    ) -> float:
        """Append a pre-encoded batch whose `tab` ids are GROUP-LOCAL
        (facade callers subtract t0). Returns seconds this writer spent
        blocked on major compactions it tripped in THIS group; accrued to
        the plane-shared per-writer blocked counter, so the plane scalar
        stays the sum over writers no matter how waits split across
        groups. Ordinary lock wait (peer appends, jit tracing) is
        deliberately NOT counted: the metric is compaction-attributed,
        like the host Tablet's (the group lock's own wait books cover
        lock contention — see obs.occupancy)."""
        n = len(rts)
        if n == 0:
            return 0.0
        rts = np.asarray(rts, np.int32)
        cols = np.asarray(cols, np.int32)
        tab = np.asarray(tab, np.int32)
        with self.lock.hold("ingest_append"):
            append = self.programs.append_step()
            with span(
                "ingest.append", cat="ingest", rows=n, writer=writer_id,
                group=self.gid,
            ) as sp:
                blocked = self._ingest_locked(append, rts, cols, tab, n)
                sp.set(blocked_s=blocked)
            self._m_blocked.inc(blocked, writer=writer_id)
            if blocked > 0.0 and self._m_group_stall is not None:
                # Group-attributed stall event: same seconds as the
                # per-writer cells, keyed by WHERE the major tripped.
                self._m_group_stall.inc(blocked, group=self.gid)
                self._m_group_stall_events.inc(group=self.gid)
            return blocked

    def _ingest_locked(self, append, rts, cols, tab, n: int) -> float:  # holds: lock
        pr = self.programs
        s = self.state
        blocked = 0.0
        b = pr.append_rows
        names = pr._append_names()
        for off in range(0, n, b):
            chunk = min(b, n - off)
            tab_chunk = tab[off : off + chunk]
            cb = np.bincount(tab_chunk, minlength=self.n_tablets)
            # Exact room check from the host-side fill mirror: flush only
            # the moment some tablet's memtable would actually overflow.
            if np.any(self._fill + cb > pr.mem_rows):
                if np.any((self._fill > 0) & (self._runs_host >= pr.max_runs)):
                    # No free run slot for a tablet that must flush: major
                    # compaction first — it BLOCKS the writer that tripped
                    # it, Accumulo's backpressure reproduced on the mesh.
                    # For the occupancy books this stretch of the ingest
                    # hold is fold work, not append work.
                    t0 = time.perf_counter()
                    with self.lock.reowner("fold_increment"):
                        with span("ingest.major", cat="ingest", group=self.gid):
                            self._run_major()
                            jax.block_until_ready(self.state["ev_base_n"])
                    blocked += time.perf_counter() - t0
                    self._m_folds.inc(source="ingest")
                with span("ingest.minor", cat="ingest", group=self.gid):
                    self._run_minor()
            pad_rts = np.zeros((b,), np.int32)
            pad_cols = np.zeros((b, pr.n_fields), np.int32)
            pad_tab = np.full((b,), -1, np.int32)  # -1: no tablet claims it
            pad_rts[:chunk] = rts[off : off + chunk]
            pad_cols[:chunk] = cols[off : off + chunk]
            pad_tab[:chunk] = tab_chunk
            s.update(
                append(
                    self._sub(names),
                    jnp.asarray(pad_rts), jnp.asarray(pad_cols), jnp.asarray(pad_tab),
                )
            )
            self._fill += cb
            self._rows_host += cb
        self._dirty = True
        self._gen["mem"] += 1  # appends touch only the memtable level
        return blocked

    # -------------------------------------------------------------- reads
    def snapshot(self) -> DistStore:
        """Snapshot this group into a query-visible DistStore — ALL levels
        of every family: base runs, sorted-run slabs, and a sealed (sorted)
        copy of the memtables. NO fold happens here: the run-aware read
        path searches every level, so a snapshot costs O(live memtable
        fill) device work (the seal sort) + a metadata flip, independent
        of base fill AND of memtable capacity — major compaction,
        threshold-driven during ingest or batched via compact(), is the
        only point where runs merge into the base.

        The whole snapshot — seal program, state references, cache flip —
        happens under the GROUP lock only (no global stop-the-world: other
        groups keep appending), so a snapshot racing concurrent writer
        ingest can never observe a torn state: every ingest call mutates
        this group's state under the same lock. Cheap no-op when nothing
        was ingested since the last snapshot."""
        with self.lock.hold("publish_seal"):
            if not self._dirty and self._published is not None:
                return self._published
            pr = self.programs
            # Fill-bounded seal: the host fill mirror is exact, so the
            # seal program sorts only the live head of each memtable
            # (pow2-bucketed to bound compilations) — a near-empty
            # memtable seals in O(fill), not O(mem_rows).
            #
            # Generation-keyed reuse: the seal depends ONLY on memtable
            # contents, so when the "mem" generation is unchanged since
            # the cached seal (the publish was forced by a fold-only
            # compact_step increment), the previous sealed arrays are
            # ALIASED — snapshots across K increments pay zero seal
            # sorts, and tests assert array identity on the reuse path.
            gen_mem = self._gen["mem"]
            if self._sealed_cache is not None and self._sealed_cache[0] == gen_mem:
                _, sealed, seal_rows = self._sealed_cache
                self._m_last_seal_rows.set_value(seal_rows)
                self._m_seal.inc(event="reuse")
            else:
                seal_rows = pr._seal_bucket(int(self._fill.max()))
                self._m_last_seal_rows.set_value(seal_rows)
                with span(
                    "ingest.seal", cat="ingest", seal_rows=seal_rows, group=self.gid
                ):
                    sealed = pr.seal_step(seal_rows)(self._sub(pr._seal_names()))
                self._sealed_cache = (gen_mem, sealed, seal_rows)
                self._m_seal.inc(event="seal")
            s = self.state
            has_ix = len(pr.families) > 1
            self._published = DistStore(
                rev_ts=s["ev_base_k"],
                cols=s["ev_base_c"],
                counts=s["ev_base_n"],
                mesh=self.mesh,
                run_rev_ts=s["ev_run_k"],
                run_cols=s["ev_run_c"],
                run_counts=s["ev_run_n"],
                mem_rev_ts=sealed["ev_sealed_k"],
                mem_cols=sealed["ev_sealed_c"],
                mem_counts=sealed["ev_sealed_n"],
                ix_keys=s["ix_base_k"] if has_ix else None,
                ix_counts=s["ix_base_n"] if has_ix else None,
                ix_run_k=s["ix_run_k"] if has_ix else None,
                ix_run_n=s["ix_run_n"] if has_ix else None,
                ix_mem_k=sealed["ix_sealed_k"] if has_ix else None,
                ix_mem_n=sealed["ix_sealed_n"] if has_ix else None,
                ag_keys=s["ag_base_k"] if has_ix else None,
                ag_vals=s["ag_base_c"] if has_ix else None,
                ag_counts=s["ag_base_n"] if has_ix else None,
                ag_run_k=s["ag_run_k"] if has_ix else None,
                ag_run_c=s["ag_run_c"] if has_ix else None,
                ag_run_n=s["ag_run_n"] if has_ix else None,
                ag_mem_k=sealed["ag_sealed_k"] if has_ix else None,
                ag_mem_c=sealed["ag_sealed_c"] if has_ix else None,
                ag_mem_n=sealed["ag_sealed_n"] if has_ix else None,
                agg_bucket_s=pr.agg_bucket_s if has_ix else None,
                gens=dict(self._gen),
            )
            self._dirty = False
            return self._published

    # ------------------------------------------------------------- warmup
    def warm_seal(self) -> None:
        with self.lock.hold("warmup"):
            pr = self.programs
            for seal_rows in pr.seal_buckets():
                pr.seal_step(seal_rows)(self._sub(pr._seal_names()))

    def warm_compaction(self) -> None:
        with self.lock.hold("warmup"):
            staged = bool(int(self._fill.max()) or int(self._runs_host.max()))
            self._run_minor()
            self._run_fold_one()
            self._run_major()
            if staged:
                self._dirty = True
                self._m_folds.inc(source="explicit")

    # -------------------------------------------------------- bookkeeping
    def has_unfolded(self) -> bool:
        """True when this group's memtables or run slots hold rows — i.e.
        compact() on it would fold something. Exact from the host-side
        mirrors: zero device syncs."""
        with self.lock.hold("bookkeeping"):
            return bool(int(self._fill.max()) or int(self._runs_host.max()))

    def fold_debt(self) -> int:
        """Deepest run-slot usage across this group's tablets (host
        mirror, free): how close its ingest is to tripping a blocking
        major (at max_runs). The facade's compact_step picks the
        most-indebted group by this signal."""
        with self.lock.hold("bookkeeping"):
            return int(self._runs_host.max())

    def counter_mirrors(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Copies of the exact per-tablet (rows, minor, major) host
        mirrors — the zero-sync source for the plane's per-tablet
        registry gauges at publish()/telemetry() boundaries."""
        with self.lock.hold("bookkeeping"):
            return self._rows_host.copy(), self._minor_host.copy(), self._major_host.copy()

    def gen_snapshot(self) -> Dict[str, int]:
        with self.lock.hold("bookkeeping"):
            return dict(self._gen)

    # --------------------------------------------------------------- fold
    def compact(self, source: str = "explicit") -> int:
        """Batched background fold of THIS group: drain memtables into
        runs (minor) and runs into the base (major) for every family —
        see DistIngestPlane.compact. Returns minor+major passes run (0
        for the no-op)."""
        with self.lock.hold("fold_increment"):
            if int(self._fill.max()) == 0 and int(self._runs_host.max()) == 0:
                return 0  # exact mirrors: nothing in memtables or run slots
            passes = 0
            with span("ingest.compact", cat="ingest", source=source, group=self.gid) as sp:
                for _ in range(3):
                    self._run_minor()
                    self._run_major()
                    passes += 1
                    if int(self._fill.max()) == 0:  # exact mirror: no device sync
                        break
                else:  # pragma: no cover — the invariant bounds this to 2 passes
                    raise RuntimeError("compact did not drain the memtables")
                sp.set(passes=passes)
            self._m_folds.inc(passes, source=source)
            self._dirty = True  # published view now points at stale levels
            return passes

    def compact_step(self, source: str = "explicit") -> int:
        """ONE bounded increment of compaction for THIS group, under only
        this group's lock — see DistIngestPlane.compact_step. Returns 1
        when an increment ran, else 0."""
        with self.lock.hold("fold_increment"):
            if int(self._runs_host.max()) > 0:
                with span(
                    "ingest.fold_increment", cat="ingest", source=source,
                    kind="fold", group=self.gid,
                ):
                    self._run_fold_one()
            elif int(self._fill.max()) > 0:
                with span(
                    "ingest.fold_increment", cat="ingest", source=source,
                    kind="minor", group=self.gid,
                ):
                    self._run_minor()
            else:
                return 0  # exact mirrors: nothing staged anywhere
            self._m_folds.inc(source=source)
            self._dirty = True  # published view now points at stale levels
            return 1

    # ---------------------------------------------------------- telemetry
    def telemetry_arrays(self) -> Dict[str, np.ndarray]:
        """Device counters of this group's tablets, fetched under the
        group lock (local tablet order == a contiguous global slice)."""
        with self.lock.hold("bookkeeping"):
            pr = self.programs
            alias = {
                "rows": "rows", "minor": "minor", "major": "major",
                "n_runs": "n_runs", "overflow": "ev_overflow",
                "mem_n": "ev_mem_n", "base_n": "ev_base_n",
            }
            out = {
                name: np.asarray(jax.device_get(self.state[key]))
                for name, key in alias.items()
            }
            for f in pr.families[1:]:
                out[f"{f.name}_overflow"] = np.asarray(
                    jax.device_get(self.state[f"{f.name}_overflow"])
                )
                out[f"{f.name}_base_n"] = np.asarray(
                    jax.device_get(self.state[f"{f.name}_base_n"])
                )
            return out


class DistIngestPlane:
    """Device-resident LSM tablet grid + its jitted ingest/compaction
    programs, sharded into ``n_groups`` independently-locked
    :class:`TabletGroup`s. T = n_devices * tablets_per_device global
    tablets; group g owns the contiguous range
    [g * T/G, (g+1) * T/G), each tablet with a memtable slab (mem_rows),
    max_runs sorted-run slots (mem_rows each) and a base run (capacity
    rows) — per family (see module docstring).

    This class is a thin FACADE: it routes batches to groups by tablet
    id, composes per-group snapshots at publish(), picks the
    most-indebted group for compact_step(), and aggregates telemetry.
    All device state and locking live in the groups; with the default
    ``n_groups=1`` every legacy single-lock behavior (state dict
    identity, "plane_lock" occupancy books, publish aliasing) is
    preserved exactly."""

    def __init__(
        self,
        mesh: Mesh,
        n_fields: int,
        capacity: int,
        tablets_per_device: int = 1,
        mem_rows: int = 4096,
        max_runs: int = 4,
        append_rows: int = 1024,
        indexed_fids: Sequence[int] = (),
        agg_bucket_s: int = DEFAULT_AGG_BUCKET_SECONDS,
        kernel_backend: str = "auto",
        n_groups: int = 1,
    ):
        if n_groups < 1:
            raise ValueError(f"n_groups must be >= 1, got {n_groups}")
        if tablets_per_device % n_groups:
            raise ValueError(
                f"n_groups={n_groups} must divide tablets_per_device="
                f"{tablets_per_device}: each group owns an equal, contiguous "
                "per-device tablet slice"
            )
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.n_fields = int(n_fields)
        self.tablets_per_device = int(tablets_per_device)
        self.n_tablets = _n_devices(mesh) * self.tablets_per_device
        self.n_groups = int(n_groups)
        self.tablets_per_group = self.n_tablets // self.n_groups
        self.capacity = int(capacity)
        self.mem_rows = int(mem_rows)
        self.max_runs = int(max_runs)
        self.append_rows = int(min(append_rows, mem_rows))
        self.indexed_fids = tuple(int(f) for f in indexed_fids)
        self.agg_bucket_s = int(agg_bucket_s)
        self.kernel_backend = kernel_backend
        # All plane counters live on a PRIVATE metrics registry (plane
        # instances in one process never share cells) and are SHARED by
        # every group; the legacy names (seal_events, blocked_seconds,
        # fold_events, ...) remain as properties over these metrics.
        # Fold accounting: every run->base fold is attributed to whoever
        # drove it — "ingest" counts BLOCKING majors tripped by a
        # writer's flush (one per major), and each `source` passed to
        # compact() ("explicit" callers, "background" for the serve
        # plane's compactor) counts that call's drain passes. Routine
        # minor flushes are not folds and are not attributed (the
        # per-tablet `minor` counter already tracks them). What matters
        # for the serve plane: the query path NEVER appears here — reads
        # cannot fold by construction — and telemetry()["fold_events"]
        # proves it.
        self.metrics = MetricsRegistry(f"plane{next(_plane_seq)}")
        self._m_seal = self.metrics.counter(
            "plane_seal_total", "publishes that ran (event=seal) vs aliased (event=reuse)"
        )
        self._m_blocked = self.metrics.counter(
            "plane_blocked_seconds_total", "writer seconds blocked on tripped majors"
        )
        # Group-attributed view of the same stalls: the per-writer cells
        # answer WHO paid, these answer WHERE — a hot tablet group whose
        # majors keep tripping shows up as one label here (and as the SLO
        # watchdog's compaction-stall rule input).
        self._m_group_stall = self.metrics.counter(
            "plane_group_stall_seconds_total",
            "writer seconds blocked on tripped majors, by tablet group",
        )
        self._m_group_stall_events = self.metrics.counter(
            "plane_group_stall_events_total",
            "ingest appends that tripped a blocking major, by tablet group",
        )
        self._m_folds = self.metrics.counter(
            "plane_fold_events_total", "run->base folds by driving source"
        )
        self._m_last_seal_rows = self.metrics.gauge(
            "plane_last_seal_rows", "event-family slots the last publish sorted"
        )
        # Per-tablet device counters surfaced WITHOUT a device sync: set
        # from the groups' exact host mirrors at publish()/telemetry()
        # boundaries only (labels carry the GLOBAL tablet id).
        self._m_tab_rows = self.metrics.gauge(
            "plane_tablet_rows", "rows appended per tablet (host mirror)"
        )
        self._m_tab_minor = self.metrics.gauge(
            "plane_tablet_minor", "minor compactions per tablet (host mirror)"
        )
        self._m_tab_major = self.metrics.gauge(
            "plane_tablet_major", "major compactions per tablet (host mirror)"
        )
        programs = _PlanePrograms(
            mesh, n_fields, capacity, self.tablets_per_device // self.n_groups,
            mem_rows, max_runs, append_rows, self.indexed_fids,
            self.agg_bucket_s, kernel_backend,
        )
        self.programs = programs
        self.families = programs.families
        self.groups: Tuple[TabletGroup, ...] = tuple(
            TabletGroup(
                g, self.n_groups, programs,
                self._m_seal, self._m_blocked, self._m_folds,
                self._m_last_seal_rows,
                m_group_stall=self._m_group_stall,
                m_group_stall_events=self._m_group_stall_events,
            )
            for g in range(self.n_groups)
        )
        # Facade-global bits: session stats and the composite-snapshot
        # cache sit under a META lock (never held across device work, and
        # never nested inside a group lock), so they stay race-free while
        # group locks split the ingest path.
        self._meta_lock = OwnedLock("plane_meta_lock")
        # Serve-plane sessions report through the same telemetry structure
        # as ingest writers (record_session); key = session id.
        self.session_stats: Dict[int, Dict[str, float]] = {}  # guarded-by: _meta_lock
        self._composite: Optional[DistStore] = None  # guarded-by: _meta_lock

    # ------------------------------------------------- legacy metric views
    # Thin views over the plane registry, kept so six PRs of tests and
    # benches read the same names they always did. blocked_seconds also
    # accepts `= 0.0` (benches zero it between rounds) — anything else
    # would silently desync the per-writer cells, so it raises.
    @property
    def seal_events(self) -> int:
        return int(self._m_seal.value(event="seal"))

    @property
    def seal_reuses(self) -> int:
        return int(self._m_seal.value(event="reuse"))

    @property
    def blocked_seconds(self) -> float:
        return self._m_blocked.total()

    @blocked_seconds.setter
    def blocked_seconds(self, v: float) -> None:
        if v != 0:
            raise ValueError("blocked_seconds can only be reset to 0")
        self._m_blocked.reset()

    @property
    def blocked_by_writer(self) -> Dict[int, float]:
        return {
            int(dict(key)["writer"]): v for key, v in self._m_blocked.cells().items()
        }

    @property
    def fold_events(self) -> Dict[str, int]:
        return {dict(key)["source"]: int(v) for key, v in self._m_folds.cells().items()}

    @property
    def last_seal_rows(self) -> int:
        return int(self._m_last_seal_rows.value())

    # -------------------------------------------- legacy single-group views
    @property
    def state(self) -> Dict[str, jax.Array]:
        """The device state dict — single-group planes only (a sharded
        plane has one state dict PER GROUP; address plane.groups[g].state
        explicitly there)."""
        if self.n_groups != 1:
            raise RuntimeError(
                "plane.state is ambiguous with n_groups > 1; "
                "use plane.groups[g].state"
            )
        return self.groups[0].state

    @property
    def _lock(self) -> OwnedLock:
        """The legacy plane lock — group 0's lock. Meaningful as THE
        plane lock only when n_groups == 1 (benches/tests key on it); a
        sharded plane has one lock per group."""
        return self.groups[0].lock

    @property
    def _dirty(self) -> bool:
        return any(g._dirty for g in self.groups)

    @_dirty.setter
    def _dirty(self, v: bool) -> None:
        for g in self.groups:
            g._dirty = bool(v)

    @property
    def _fill(self) -> np.ndarray:
        if self.n_groups == 1:
            return self.groups[0]._fill
        return np.concatenate([g._fill for g in self.groups])

    @property
    def _runs_host(self) -> np.ndarray:
        if self.n_groups == 1:
            return self.groups[0]._runs_host
        return np.concatenate([g._runs_host for g in self.groups])

    @classmethod
    def for_store(cls, store, mesh: Mesh, capacity: int, **kw) -> "DistIngestPlane":
        """Plane bound to a host store's schema: maintains index postings
        and aggregate counts for the store's indexed fields, with the
        store's aggregate bucketing (so host and dist densities agree)."""
        kw.setdefault(
            "indexed_fids", tuple(int(f) for f in store._indexed_field_ids)
        )
        kw.setdefault("agg_bucket_s", store.agg_bucket_seconds)
        return cls(mesh, store.schema.n_fields, capacity, **kw)

    # ------------------------------------------------------------- ingest
    def ingest(
        self, rts: np.ndarray, cols: np.ndarray, tab: np.ndarray, writer_id: int = 0
    ) -> float:
        """Append a pre-encoded, pre-sharded batch. rts int32 reversed
        timestamps; cols (n, F) int32 codes; tab (n,) int32 GLOBAL tablet
        ids. Routes each row to the group owning its tablet (group =
        tab // tablets_per_group) — rows for different groups append
        under different locks, so writers whose batches land on disjoint
        groups proceed fully concurrently. Returns seconds this writer
        spent blocked on major compactions it tripped (backpressure),
        summed across the groups this batch touched; also accrued to
        blocked_by_writer[writer_id], with the plane scalar kept as the
        sum over writers."""
        n = len(rts)
        if n == 0:
            return 0.0
        rts = np.asarray(rts, np.int32)
        cols = np.asarray(cols, np.int32)
        tab = np.asarray(tab, np.int32)
        if self.n_groups == 1:
            return self.groups[0].ingest(rts, cols, tab, writer_id=writer_id)
        gids = tab // np.int32(self.tablets_per_group)
        blocked = 0.0
        for g in self.groups:
            m = gids == g.gid
            if not m.any():
                continue
            blocked += g.ingest(
                rts[m], cols[m], (tab[m] - np.int32(g.t0)), writer_id=writer_id
            )
        return blocked

    # -------------------------------------------------------------- reads
    def publish(self) -> DistStore:
        """Snapshot the plane into a query-visible DistStore — ALL levels
        of every family, composed from per-group zero-copy snapshots with
        NO global stop-the-world: each group seals under only its own
        lock (concurrent writers on other groups never stall), and a
        group that is clean since its last snapshot ALIASES its previous
        arrays. Single-group planes return the group's DistStore directly
        (the legacy zero-copy snapshot, identity-preserving); sharded
        planes return a COMPOSITE DistStore whose ``groups`` tuple holds
        the per-group sub-stores in global tablet order, with per-group
        generation tags under ``gens["g<i>"]`` — the read path
        (core/dist_query.py) fans out over the sub-stores and each
        sub-store keeps its own planner density cache, so untouched
        groups' caches survive publishes of busy ones."""
        with span("ingest.publish", cat="ingest"):
            if self.n_groups == 1:
                out = self.groups[0].snapshot()
                self._update_tablet_gauges()
                return out
            subs = tuple(g.snapshot() for g in self.groups)
            self._update_tablet_gauges()
            with self._meta_lock.hold("publish_compose"):
                cached = self._composite
                if cached is not None and all(
                    a is b for a, b in zip(cached.groups, subs)
                ):
                    return cached
                self._composite = DistStore(
                    mesh=self.mesh,
                    groups=subs,
                    gens={
                        f"g{g.gid}": dict(sub.gens)
                        for g, sub in zip(self.groups, subs)
                    },
                )
                return self._composite

    def precompile(self) -> None:
        """Compile every compaction and seal program concurrently (see
        _PlanePrograms.precompile); warm_compaction() and warm_seal() then
        only execute them. Shared by all groups: shapes are identical."""
        self.programs.precompile()

    def warm_seal(self) -> None:
        """Pre-compile (and once-execute) the fill-bounded seal program
        for every pow2 bucket up to mem_rows — log2-many variants.
        Serving deployments call this once at startup so no publish ever
        pays an XLA compile mid-query (a cold bucket otherwise lands its
        compile time in some session's time-to-first-result). The step
        cache is shared across groups, so later groups replay compiled
        programs (one device execution each, no new traces)."""
        for g in self.groups:
            g.warm_seal()

    def warm_compaction(self) -> None:
        """Pre-compile (and once-execute) every compaction program —
        minor flush, incremental fold step, full major — so no later
        background increment or blocking major pays an XLA compile (a
        cold fold program otherwise lands its whole compile time inside
        one \"bounded\" increment). Runs the real programs on each
        group's current state: anything staged gets drained exactly like
        compact(), and is attributed the same way; on a drained plane
        all three are device no-ops."""
        for g in self.groups:
            g.warm_compaction()

    def has_unfolded(self) -> bool:
        """True when ANY group's memtables or run slots hold rows — i.e.
        compact() would actually fold something. Exact from the host-side
        fill/run mirrors: zero device syncs, so the serve plane's
        background compactor can poll it from its idle loop for free."""
        return any(g.has_unfolded() for g in self.groups)

    def fold_debt(self) -> int:
        """Deepest run-slot usage across ALL tablets of ALL groups (host
        mirrors, free): how close ingest is to tripping a blocking major
        (at max_runs). The background compactor folds urgently above its
        debt threshold and otherwise waits for a sustained idle window —
        a major costs seconds of device time at scale, so WHEN it runs
        is the whole game."""
        return max(g.fold_debt() for g in self.groups)

    def compact(self, source: str = "explicit") -> int:
        """Batched background fold of EVERY group: drain memtables into
        runs (minor) and runs into the base (major) for every family.
        This — plus the threshold-driven majors ingest itself trips — is
        the ONLY place runs fold into the base; publish() never does.
        Call it off the query path (the serve plane's
        BackgroundCompactor, an idle writer) to keep run counts low;
        queries stay exact either way, the fold only moves where rows
        live. No-op (and keeps the published-view caches) when there is
        nothing to fold.

        `source` attributes the fold in telemetry()["fold_events"]
        (see __init__); returns the number of minor+major passes run
        summed over groups (0 for the no-op), so callers like the
        compactor can count real folds without a telemetry round trip."""
        return sum(g.compact(source) for g in self.groups)

    def compact_step(self, source: str = "explicit") -> int:
        """ONE bounded increment of compaction — the preemptible unit the
        serve plane's BackgroundCompactor interleaves between session
        turns. The MOST-INDEBTED group is picked (deepest run-slot
        usage, ties broken toward staged memtable rows then lower group
        id) and exactly one device program runs under ONLY that group's
        lock — a fold increment never stalls writers on the other G-1
        groups. Per group the increment is the same preemptible unit as
        before:

          * run slots occupied  -> fold every tablet's TOP run slot into
            its base (one 2-way O(capacity + mem_rows) merge per family,
            vs compact()'s all-runs k-way fold),
          * else memtable rows  -> one minor flush (memtables -> a run
            slot; the next calls fold it),
          * else                -> no-op, return 0.

        Any prefix of increments leaves a fully consistent LSM (base +
        fewer runs) that every dist_query read primitive already handles
        — an interrupted major is just a database with lower fold debt,
        so a fresh query can preempt between ANY two increments and
        still read exact results. Calling it until 0 is equivalent to
        compact() (per-tablet multiset agreement; equal-key order may
        differ, which no query primitive observes — asserted against the
        numpy oracle in tests). Returns 1 when an increment ran, else 0;
        increments are attributed to fold_events[source] like compact()
        passes."""
        if self.n_groups == 1:
            return self.groups[0].compact_step(source)
        # Debt signals are read per group under its own lock; the pick can
        # race a concurrent writer, so each candidate re-checks under its
        # lock (compact_step returns 0 if its group drained meanwhile) and
        # the scan falls through to the next-most-indebted group.
        ranked = sorted(
            self.groups,
            key=lambda g: (g.fold_debt(), g.has_unfolded()),
            reverse=True,
        )
        for g in ranked:
            if g.compact_step(source):
                return 1
        return 0

    def record_session(self, session_id: int, stats: Dict[str, float]) -> None:
        """Serve-plane hook: a QuerySession reports its telemetry (batches
        served, time-to-first-result, queue-wait seconds, ...) into the
        plane, so clients of the query-serving plane and ingest writers
        surface through ONE structure — telemetry()["sessions"] next to
        ["blocked_seconds_per_writer"]. Guarded by the facade's meta
        lock, NOT any group lock: session merges stay race-free no matter
        which groups concurrent turns touch. Bounded: only the most
        recent 1024 sessions are retained (insertion order), so
        per-connection sessions on a long-lived service don't grow the
        plane without limit."""
        with self._meta_lock.hold("bookkeeping"):
            self.session_stats.pop(int(session_id), None)  # refresh position
            self.session_stats[int(session_id)] = dict(stats)
            while len(self.session_stats) > 1024:
                self.session_stats.pop(next(iter(self.session_stats)))

    def _update_tablet_gauges(self) -> None:
        """Snapshot the groups' exact per-tablet host mirrors into the
        plane registry gauges (labels = GLOBAL tablet id). Zero device
        syncs: the mirrors are maintained in lockstep with the device
        programs, and this runs only at publish()/telemetry() boundaries."""
        for g in self.groups:
            rows, minor, major = g.counter_mirrors()
            for i in range(len(rows)):
                t = g.t0 + i
                self._m_tab_rows.set(float(rows[i]), tablet=t)
                self._m_tab_minor.set(float(minor[i]), tablet=t)
                self._m_tab_major.set(float(major[i]), tablet=t)

    def telemetry(self) -> Dict[str, np.ndarray]:
        """Per-tablet device counters (the paper's backpressure signals)
        in GLOBAL tablet order (groups own contiguous ranges, so
        per-group arrays concatenate in group order), plus per-writer
        blocked-seconds (the §IV-A per-client curve — the per-writer
        cells are plane-shared, so they sum to the scalar even when one
        writer's waits split across several groups).

        Since the observability PR the scalar counters here are views of
        the plane's metrics registry (`self.metrics`); this dict remains
        the stable legacy surface, and repro.obs.metrics_snapshot() sees
        the same cells without a device sync."""
        parts = [g.telemetry_arrays() for g in self.groups]
        out: Dict[str, np.ndarray] = {
            name: np.concatenate([p[name] for p in parts]) for name in parts[0]
        }
        out["blocked_seconds"] = np.float64(self.blocked_seconds)
        out["blocked_seconds_per_writer"] = dict(self.blocked_by_writer)
        # One reporting structure for both planes: ingest writers
        # above, serve-plane query sessions + fold attribution below.
        with self._meta_lock.hold("bookkeeping"):
            out["sessions"] = {k: dict(v) for k, v in self.session_stats.items()}
        out["fold_events"] = dict(self.fold_events)
        # Snapshot-aliasing counters: level generations plus how many
        # publishes re-ran vs aliased the seal sort (flat seal_events
        # across fold-only increments == no per-increment device
        # work, the acceptance bar for bounded-stall compaction).
        if self.n_groups == 1:
            out["level_gen"] = self.groups[0].gen_snapshot()
        else:
            out["level_gen"] = {
                f"g{g.gid}": g.gen_snapshot() for g in self.groups
            }
        out["seal_events"] = int(self.seal_events)
        out["seal_reuses"] = int(self.seal_reuses)
        self._update_tablet_gauges()
        return out


class DistBatchWriter(BatchWriter):
    """Client-side ingest writer for the device plane (paper §II: one
    BatchWriter per parallel ingest client). Buffers parsed events exactly
    like the host BatchWriter; a flush encodes via the store's dictionaries,
    shards by row hash, and appends through the plane — the row hash picks
    a GLOBAL tablet, whose owning TabletGroup's lock is the only one the
    append takes, so writers whose hashes land on disjoint groups proceed
    concurrently; a flush still blocks while a major compaction it
    tripped drains, which is the measured backpressure.

    writer_id keys the plane's per-writer blocked-seconds telemetry (and
    salts the row hash); when omitted, each writer gets a fresh unique id,
    so parallel clients never collapse into one telemetry bucket."""

    _next_id = itertools.count()

    def __init__(
        self,
        store,
        plane: DistIngestPlane,
        batch_rows: int = 4096,
        metrics: Optional[IngestMetrics] = None,
        writer_id: Optional[int] = None,
    ):
        super().__init__(store, batch_rows=batch_rows, metrics=metrics)
        self.plane = plane
        if writer_id is None:
            writer_id = next(DistBatchWriter._next_id)
        self._writer_id = np.int64(writer_id)
        self._count = 0

    def _write(self, ts: np.ndarray, values) -> float:
        ts = np.asarray(ts, dtype=np.int64)
        if np.any(ts < 0) or np.any(ts > keypack.TS_MAX):
            # Same contract as EventStore.ingest_encoded — out-of-range
            # timestamps must not silently wrap into negative rev_ts.
            raise ValueError("timestamp out of 30-bit store range")
        cols = self.store.encode_events(ts, values)
        n = len(ts)
        # Row hash decides the tablet: content + per-writer nonce, so
        # identical events still spread uniformly (the paper's random
        # sharding; shard id is implicit in tablet choice here).
        nonce = np.arange(self._count, self._count + n, dtype=np.int64)
        self._count += n
        h = keypack.short_hash(
            *(cols[:, j] for j in range(cols.shape[1])), ts, nonce, self._writer_id
        )
        tab = (h % self.plane.n_tablets).astype(np.int32)
        rts = keypack.rev_ts(np.asarray(ts, np.int64)).astype(np.int32)
        return self.plane.ingest(rts, cols, tab, writer_id=int(self._writer_id))


def check_tablet_guidance(n_tablets: int, n_writers: int) -> bool:
    """Paper sizing guidance, lifted to the mesh: tablet count at least
    half the parallel writer count (the shard-vs-client rule, one home)."""
    return check_shard_guidance(n_tablets, n_writers)
