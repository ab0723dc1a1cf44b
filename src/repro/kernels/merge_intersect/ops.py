"""Public sorted-set ops used by the query planner's combine step.

intersect_sorted: A ∩ B over sorted int64 packed-key vectors (the planner's
AND path — paper Fig 2). union_sorted: A ∪ B (the OR path; bandwidth-bound
merge, no kernel warranted — jnp sort of the concatenation).

Backend policy (kernels/common.py::resolve_backend): "auto" is the jnp
reference on every platform — the kernel does not compile for TPU
(lowering hits a RecursionError; ROADMAP D2). An explicit "pallas"
request needs the probe set in VMEM and raises ValueError past the
documented MAX_VMEM_KEYS cap instead of switching to the reference.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..common import pallas_interpret, pow2 as _pow2, resolve_backend, split_key_lanes as _split
from .merge_intersect import BLOCK, intersect_mask_pallas
from .ref import intersect_mask_ref

MAX_VMEM_KEYS = 1 << 20  # 2 lanes * 4 B * 1M = 8 MiB resident in VMEM


def intersect_sorted(a: np.ndarray, b: np.ndarray, backend: str = "auto") -> np.ndarray:
    """Intersection of two sorted (ascending, non-negative) int64 key sets.
    Returns sorted int64 array."""
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    if a.size == 0 or b.size == 0:
        return np.empty(0, np.int64)
    # Probe the smaller set from the larger: kernel cost n log m.
    if a.size < b.size:
        a, b = b, a
    backend = resolve_backend(backend)
    if backend == "pallas" and b.size > MAX_VMEM_KEYS:
        raise ValueError(
            f"merge_intersect pallas: probe set of {b.size} keys exceeds the VMEM "
            f"cap MAX_VMEM_KEYS={MAX_VMEM_KEYS}; use backend='ref'"
        )
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    if backend == "ref":
        # Pow2-bucket both sides to avoid per-shape retraces (sentinels:
        # A pads with -1 hi / never matches; B pads with +INF order).
        na, nb = a.size, b.size
        pa, pb = _pow2(na), _pow2(nb)
        ah = np.full(pa, -1, np.int32); ah[:na] = a_hi
        al = np.zeros(pa, np.int32); al[:na] = a_lo
        bh = np.full(pb, np.iinfo(np.int32).max, np.int32); bh[:nb] = b_hi
        bl = np.full(pb, -1, np.int32); bl[:nb] = b_lo
        mask = np.asarray(intersect_mask_ref(ah, al, bh, bl))[:na]
        return a[mask]
    # Pallas: pad A to the block multiple with sentinel keys that cannot
    # match (hi = -1 never occurs: real hi >= 0); pad B to a power of two
    # with +INF in (hi, lo-unsigned) order.
    n_pad = ((a.size + BLOCK - 1) // BLOCK) * BLOCK
    m_pad = _pow2(b.size)
    ah = np.full(n_pad, -1, np.int32)
    al = np.zeros(n_pad, np.int32)
    ah[: a.size] = a_hi
    al[: a.size] = a_lo
    bh = np.full(m_pad, np.iinfo(np.int32).max, np.int32)
    bl = np.full(m_pad, -1, np.int32)  # 0xFFFFFFFF: max in unsigned order
    bh[: b.size] = b_hi
    bl[: b.size] = b_lo
    mask = np.asarray(
        intersect_mask_pallas(
            jnp.asarray(ah), jnp.asarray(al), jnp.asarray(bh), jnp.asarray(bl),
            interpret=pallas_interpret(),
        )
    )[: a.size]
    return a[mask]


def union_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Union of two sorted int64 key sets (planner OR path)."""
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    if a.size == 0:
        return np.unique(b)
    if b.size == 0:
        return np.unique(a)
    return np.unique(np.concatenate([a, b]))
