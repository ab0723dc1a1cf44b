"""Helpers shared across kernel subpackages — the backend policy, pow2
bucketing, key-lane splitting (core-import-free: the
kernels package must never import repro.core at module scope — core's
__init__ imports the query/iterator modules that need the kernels)."""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np

BACKENDS = ("ref", "pallas")


def resolve_backend(backend: str) -> str:
    """The one kernel backend policy every ops.py entry point follows.

    "auto" resolves to the jnp reference on EVERY platform: none of the
    Pallas kernels compiles for TPU yet (the compiler errors are recorded
    in ROADMAP S6/S8/D2), so auto never selects one. "pallas" is an
    explicit request — native on TPU, interpret mode elsewhere
    (pallas_interpret) — and an explicit request that a kernel cannot
    serve (a VMEM cap) raises instead of switching to the reference."""
    if backend == "auto":
        return "ref"
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}: auto | ref | pallas")
    return backend


def pallas_interpret() -> bool:
    """Pallas runs natively only on a TPU; everywhere else in interpret mode."""
    return jax.default_backend() != "tpu"


def pow2(n: int) -> int:
    """Smallest power of two >= n. Every kernel pads shapes to pow2
    buckets to bound the retrace count; one definition, not one clone
    per package (enforced by reprolint's kernel-contract rule)."""
    p = 1
    while p < n:
        p *= 2
    return p


def split_key_lanes(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 packed keys -> (hi, lo) int32 lanes. TPU-native carry format:
    kernels only ever see 32-bit lanes; the lo lane's bit pattern is
    preserved via a uint32 view (negative int32 == high-bit-set lane)."""
    keys = np.asarray(keys, dtype=np.int64)
    hi = (keys >> 32).astype(np.int32)
    lo = (keys & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    return hi, lo
