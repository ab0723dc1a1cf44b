"""Pure-jnp oracle of the k-way merge rank computation.

merge_ranks_keys is traceable (jit / shard_map safe); merge_ranks_ref is
its (hi, lo)-lane form, the oracle the Pallas rank kernel is tested
against. The device plane's own merges do not rank: their jnp path is a
stable sort (ops.py::sort_with_payload), because searchsorted's gather
loop runs far slower than a sort on a TPU at tablet sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def merge_ranks_keys(keys):
    """keys (K, R): each row sorted ascending, sentinel-padded (sentinel =
    dtype max, sorting after every real key). Returns int32 (K, R) output
    ranks — a permutation of [0, K*R), stable in (run, index) order."""
    k, r = keys.shape
    own = jnp.broadcast_to(jnp.arange(r, dtype=jnp.int32), (k, r))
    ranks = own
    for i in range(k):
        for j in range(k):
            if i == j:
                continue
            side = "right" if i < j else "left"  # earlier runs win ties
            cnt = jnp.searchsorted(keys[i], keys[j], side=side).astype(jnp.int32)
            ranks = ranks.at[j].add(cnt)
    return ranks


def _join(hi, lo):
    return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & 0xFFFFFFFF)


@jax.jit
def merge_ranks_ref(runs_hi, runs_lo):
    """(hi, lo)-lane oracle for merge_ranks_pallas: reconstruct the packed
    int64 keys and rank via searchsorted."""
    return merge_ranks_keys(_join(runs_hi, runs_lo))
