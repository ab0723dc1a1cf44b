"""Public combiner op: sums counts of duplicate keys in a sorted run.

Pallas path: tile-local segmented sums from the kernel + an O(n_tiles)
stitching epilogue for keys straddling tile boundaries. Default ("auto",
kernels/common.py::resolve_backend): the jnp reference on every platform
(identical output, asserted in tests) — the kernel does not compile for
TPU (`scatter` is not implemented in Mosaic; ROADMAP D2)."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from ..common import pallas_interpret, pow2 as _pow2, resolve_backend, split_key_lanes as _split
from .aggregate_combine import BLOCK, combine_blocks_pallas
from .ref import combine_blocks_ref


def combine_sorted_counts(
    keys: np.ndarray, counts: np.ndarray, backend: str = "auto"
) -> Tuple[np.ndarray, np.ndarray]:
    """(sorted int64 keys with possible duplicates, int32 counts) ->
    (unique sorted keys, summed counts)."""
    keys = np.asarray(keys, dtype=np.int64)
    counts = np.asarray(counts, dtype=np.int32)
    n = keys.size
    if n == 0:
        return keys, counts
    hi, lo = _split(keys)
    backend = resolve_backend(backend)
    if backend == "ref":
        # Pow2-bucket to avoid per-shape retraces. Pad keys with INT64_MAX
        # pairs and zero counts: they form trailing segments summing to 0
        # that the [:n] slice drops.
        n_pad = _pow2(n)
        if n_pad != n:
            mx = np.iinfo(np.int32).max
            hi = np.concatenate([hi, np.full(n_pad - n, mx, np.int32)])
            lo = np.concatenate([lo, np.full(n_pad - n, mx, np.int32)])
            counts = np.concatenate([counts, np.zeros(n_pad - n, np.int32)])
        heads, sums = combine_blocks_ref(jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(counts))
        heads = np.asarray(heads)[:n]
        sums = np.asarray(sums)[:n]
        return keys[heads], sums[heads]
    n_pad = ((n + BLOCK - 1) // BLOCK) * BLOCK
    hi_p = np.full(n_pad, np.iinfo(np.int32).max, np.int32)
    lo_p = np.full(n_pad, np.iinfo(np.int32).max, np.int32)
    cnt_p = np.zeros(n_pad, np.int32)
    hi_p[:n], lo_p[:n], cnt_p[:n] = hi, lo, counts
    heads, sums = combine_blocks_pallas(
        jnp.asarray(hi_p), jnp.asarray(lo_p), jnp.asarray(cnt_p),
        interpret=pallas_interpret(),
    )
    heads = np.asarray(heads).copy()
    sums = np.asarray(sums).copy()
    # Stitch tile boundaries: if the first key of tile t equals the last key
    # of tile t-1, fold its head sum into the open segment and clear the
    # flag. O(n_tiles) host loop — the classic two-level reduction epilogue.
    for t in range(1, n_pad // BLOCK):
        i = t * BLOCK
        if i >= n:
            break
        if keys[i] == keys[i - 1]:
            # Find the open segment's head (last head position before i).
            h = i - 1
            while not heads[h]:
                h -= 1
            sums[h] += sums[i]
            heads[i] = False
            sums[i] = 0
    heads = heads[:n]
    sums = sums[:n]
    return keys[heads], sums[heads]
