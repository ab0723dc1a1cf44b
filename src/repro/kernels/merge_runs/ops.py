"""Public k-way sorted-run merge — major compaction's data plane.

merge_sorted_runs: host entry point (numpy in/out) used by tables.py
tablet compaction on 64-bit packed keys: output ranks by searchsorted
(or the Pallas rank kernel), then one scatter of keys + payload.
merge_sorted_device / merge_pair_device / merge_base_runs_device:
traceable forms used per tablet inside the dist_ingest shard_map
compaction programs. Their jnp path is ONE stable sort of the
concatenated runs (equal to their stable merge:
earlier runs win ties) — no scatter, and no gather unless the payload is
wider than one column: a TPU runs both far slower than a sort at tablet
sizes. Their Pallas path computes ranks in the kernel and scatters.
Results are identical (asserted in tests).

Backend policy is kernels/common.py::resolve_backend: "auto" is the jnp
reference on every platform (the rank kernel does not compile for TPU:
its block shape breaks the (8, 128) tiling rule and its binary search
gathers from VMEM — ROADMAP S8). An explicit "pallas" request runs the
kernel (interpret mode off TPU) and raises ValueError past the VMEM cap
(the full key lanes stay resident) instead of switching to the reference.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import pallas_interpret, pow2 as _pow2, resolve_backend, split_key_lanes
from .merge_runs import merge_ranks_pallas

# 2 lanes * 4 B * 1M keys = 8 MiB resident in VMEM.
MAX_VMEM_KEYS = 1 << 20

_SENTINEL64 = np.iinfo(np.int64).max


def _check_vmem(n_keys: int) -> None:
    """The rank kernel keeps every run's key lanes resident in VMEM."""
    if n_keys > MAX_VMEM_KEYS:
        raise ValueError(
            f"merge_runs pallas: {n_keys} padded keys exceed the VMEM cap "
            f"MAX_VMEM_KEYS={MAX_VMEM_KEYS}; use backend='ref'"
        )


def merge_sorted_runs(
    runs: Sequence[Tuple[np.ndarray, np.ndarray]], backend: str = "auto"
) -> Tuple[np.ndarray, np.ndarray]:
    """Merge K sorted (keys int64 [n_i], cols [n_i, w]) runs into one.

    Keys ascend within each run (duplicates allowed — the merge is stable
    in run order, matching the concat+stable-argsort it replaces). Returns
    (keys [n], cols [n, w]) with n = sum n_i.
    """
    runs = [(np.asarray(k, np.int64), np.asarray(c)) for k, c in runs]
    if not runs:
        return np.empty(0, np.int64), np.empty((0, 0), np.int32)
    # Width and dtype come from the input even when every run is empty.
    w = runs[0][1].shape[1]
    col_dtype = runs[0][1].dtype
    runs = [(k, c) for k, c in runs if k.size]
    if not runs:
        return np.empty(0, np.int64), np.empty((0, w), col_dtype)
    if len(runs) == 1:
        return runs[0]
    k = len(runs)
    n_total = sum(kk.size for kk, _ in runs)

    backend = resolve_backend(backend)
    if backend == "pallas":
        _check_vmem(k * _pow2(max(kk.size for kk, _ in runs)))

    if backend == "ref":
        # CPU: the same rank computation with unpadded numpy searchsorted
        # (per-run lengths kept exact — no sentinel work, no dispatch).
        ranks = [
            np.arange(kk.size, dtype=np.int64)
            + sum(
                np.searchsorted(other, kk, side="right" if i < j else "left")
                for i, (other, _) in enumerate(runs)
                if i != j
            )
            for j, (kk, _) in enumerate(runs)
        ]
        out_keys = np.empty(n_total, np.int64)
        out_cols = np.empty((n_total, w), col_dtype)
        for (kk, cc), rk in zip(runs, ranks):
            out_keys[rk] = kk
            out_cols[rk] = cc
        return out_keys, out_cols

    # Pallas: sentinel-pad to a (k, pow2) grid of (hi, lo) lanes.
    r = _pow2(max(kk.size for kk, _ in runs))
    keys_pad = np.full((k, r), _SENTINEL64, np.int64)
    cols_pad = np.zeros((k, r, w), col_dtype)
    for i, (kk, cc) in enumerate(runs):
        keys_pad[i, : kk.size] = kk
        cols_pad[i, : kk.size] = cc
    hi, lo = split_key_lanes(keys_pad.reshape(-1))
    ranks = np.asarray(
        merge_ranks_pallas(
            jnp.asarray(hi.reshape(k, r)), jnp.asarray(lo.reshape(k, r)),
            interpret=pallas_interpret(),
        )
    )
    # Scatter epilogue: ranks are a permutation of [0, k*r); sentinels
    # land as a contiguous tail past n_total and are sliced away.
    flat = ranks.reshape(-1)
    out_keys = np.empty(k * r, np.int64)
    out_keys[flat] = keys_pad.reshape(-1)
    out_cols = np.empty((k * r, w), col_dtype)
    out_cols[flat] = cols_pad.reshape(-1, w)
    return out_keys[:n_total], out_cols[:n_total]


def sort_with_payload(keys, cols):
    """Stable sort of keys (N,) with their payload rows cols (N, F) —
    applied to concatenated sorted runs, their stable merge (earlier runs
    win ties). Payloads of width <= 1 ride the sort as operands; wider
    ones follow the sorted permutation by one row gather."""
    f = cols.shape[-1]
    if f <= 1:
        out = lax.sort((keys,) + ((cols[:, 0],) if f else ()), num_keys=1, is_stable=True)
        return out[0], (out[1][:, None] if f else cols)
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    sorted_keys, perm = lax.sort((keys, iota), num_keys=1, is_stable=True)
    return sorted_keys, cols[perm]


def merge_pair_device(a_keys, a_cols, b_keys, b_cols, backend: str = "auto"):
    """Resumable 2-way merge: fold ONE sorted run into a base, traceable
    (jit / shard_map safe) — the entry point of incremental major
    compaction (DistIngestPlane.compact_step folds one run slot per call,
    so the preemption unit is one of these merges instead of the whole
    k-way fold).

    a_keys (Ca,), b_keys (Cb,): each sorted ascending with the dtype-max
    sentinel past the live fill (callers mask stale slots first); a_cols
    (Ca, W) / b_cols (Cb, W) travel with their keys. Returns the merged
    (Ca+Cb,) keys and (Ca+Cb, W) cols — all real keys first (stable:
    a-side wins ties), sentinels as a contiguous tail. Backend policy is
    merge_sorted_device's."""
    if resolve_backend(backend) == "ref":
        return sort_with_payload(
            jnp.concatenate([a_keys, b_keys]), jnp.concatenate([a_cols, b_cols])
        )
    ca, cb = a_keys.shape[0], b_keys.shape[0]
    w = a_cols.shape[-1]
    l2 = _pow2(max(ca, cb))
    sentinel = jnp.asarray(jnp.iinfo(a_keys.dtype).max, a_keys.dtype)
    pk = jnp.full((2, l2), sentinel, a_keys.dtype)
    pk = pk.at[0, :ca].set(a_keys).at[1, :cb].set(b_keys)
    pc = jnp.zeros((2, l2, w), a_cols.dtype)
    pc = pc.at[0, :ca].set(a_cols).at[1, :cb].set(b_cols)
    mk, mc = merge_sorted_device(pk, pc, backend=backend)
    return mk[: ca + cb], mc[: ca + cb]


def merge_base_runs_device(base_keys, base_cols, run_keys, run_cols, backend: str = "auto"):
    """Whole major compaction of one tablet, traceable: the base (C,) /
    (C, W) merged with K sentinel-masked runs (K, R) / (K, R, W). Returns
    (C+K*R,) keys and (C+K*R, W) cols — the base wins ties, then earlier
    runs; sentinels as a contiguous tail. On "auto" that is ONE stable
    sort of the concatenation; the rank kernel merges the runs first and
    then the result into the base (merge_pair_device), with the same
    output."""
    k, r = run_keys.shape
    w = run_cols.shape[-1]
    if resolve_backend(backend) == "ref":
        return sort_with_payload(
            jnp.concatenate([base_keys, run_keys.reshape(-1)]),
            jnp.concatenate([base_cols, run_cols.reshape(k * r, w)]),
        )
    mk, mc = merge_sorted_device(run_keys, run_cols, backend=backend)
    return merge_pair_device(base_keys, base_cols, mk, mc, backend=backend)


def merge_window_keys(keys, start: int, length: int):
    """Windowed (rank-resumable) form of the k-way merge: output ranks
    [start, start+length) only. keys (K, R) sorted ascending per row,
    sentinel-padded. Concatenating consecutive windows reproduces the
    full merged key sequence exactly (asserted in tests) — the
    finer-than-one-run preemption granularity available if a single
    base+run fold ever outgrows its stall budget. Ranks come from the
    same computation both backends share, so the window content never
    depends on backend."""
    from .ref import merge_ranks_keys

    ranks = merge_ranks_keys(keys).reshape(-1)
    sentinel = jnp.asarray(jnp.iinfo(keys.dtype).max, keys.dtype)
    in_win = (ranks >= start) & (ranks < start + length)
    dest = jnp.where(in_win, ranks - start, jnp.int32(length))
    return jnp.full((length,), sentinel, keys.dtype).at[dest].set(
        keys.reshape(-1), mode="drop"
    )


def _device_lanes(run_keys):
    """Split device-tablet keys into the (hi, lo) int32 lane pair the Pallas
    rank kernel consumes. int32 keys (event tablets: non-negative rev_ts,
    INT32_MAX sentinel) ride the lo lane with hi = 0 — signed and unsigned
    order coincide for non-negative values, and the sentinel stays maximal.
    int64 keys (index/aggregate tablets: packed 62-bit keys, INT64_MAX
    sentinel) split exactly like the host path."""
    if run_keys.dtype == jnp.int32:
        return jnp.zeros_like(run_keys), run_keys
    hi = (run_keys >> 32).astype(jnp.int32)
    lo = (run_keys & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32)
    return hi, lo


def merge_sorted_device(run_keys, run_cols, backend: str = "auto"):
    """Traceable k-way merge for device tablets (jit / shard_map safe).

    run_keys (K, R) int32 or int64: each row sorted ascending, padded with
    the dtype-max sentinel. run_cols (K, R, F) payload (F may be 0).
    Returns the merged (K*R,) keys and (K*R, F) cols — sentinels as a
    contiguous tail.

    Backend policy matches merge_sorted_runs: "auto" is the jnp stable
    sort (sort_with_payload); "pallas" runs the rank kernel (interpret mode off
    TPU) and raises past the VMEM-resident key-lane cap. Both give the
    same merge (asserted in tests), so the choice never changes results.
    """
    k, r = run_keys.shape
    f = run_cols.shape[-1]
    backend = resolve_backend(backend)
    r2 = _pow2(r)
    if backend == "pallas":
        _check_vmem(k * r2)
    if backend == "ref":
        return sort_with_payload(run_keys.reshape(-1), run_cols.reshape(k * r, f))
    # Sentinel-pad each run to a power of two: added sentinels sort after
    # every real key, so real ranks are unchanged and sentinels (original
    # and pad) fill the permutation's tail. Scatter at the padded length,
    # then slice — real keys all rank below k*r, so the slice recovers the
    # unpadded contract exactly.
    sentinel = jnp.asarray(jnp.iinfo(run_keys.dtype).max, run_keys.dtype)
    padded = jnp.full((k, r2), sentinel, run_keys.dtype).at[:, :r].set(run_keys)
    padded_cols = jnp.zeros((k, r2, f), run_cols.dtype).at[:, :r].set(run_cols)
    hi, lo = _device_lanes(padded)
    ranks = merge_ranks_pallas(hi, lo, interpret=pallas_interpret()).reshape(-1)
    out_keys = jnp.full((k * r2,), sentinel, run_keys.dtype).at[ranks].set(padded.reshape(-1))
    out_cols = jnp.zeros((k * r2, f), run_cols.dtype).at[ranks].set(padded_cols.reshape(k * r2, f))
    return out_keys[: k * r], out_cols[: k * r]
