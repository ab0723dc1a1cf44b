"""Pallas TPU kernels for the store's compute hot-spots — the pieces the
paper implements as server-side Accumulo iterators/combiners:

  filter_scan        server-side filter iterator (WholeRowIterator subclass)
                     -> vectorized predicate program over columnar VMEM tiles
  merge_intersect    client-side index key-set intersection (query plan AND)
                     -> blockwise binary-search membership over sorted keys
  aggregate_combine  combiner framework (count aggregation)
                     -> block-segmented sum over sorted (key, count) runs
  combine_scan       fused filter + combiner (scan-time aggregation for the
                     iterator stack) -> one VMEM pass per tablet tile

Each subpackage: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper), ref.py (pure-jnp oracle used for allclose validation).

Backend policy (common.py::resolve_backend): backend="auto" is the jnp
reference on EVERY platform — none of these kernels compiles for TPU yet
(ROADMAP S6/S8/D2 record the compiler errors). backend="pallas" is an
explicit request: native on TPU, interpret mode elsewhere, and a request
past a kernel's VMEM cap raises instead of switching to the reference.

All kernels operate on int32 lanes only (dictionary codes / split key
lanes) — the packed int64 keys never enter a kernel, by design (TPU-native
layout; see DESIGN.md hardware-adaptation table).
"""
from . import aggregate_combine, combine_scan, filter_scan, merge_intersect  # noqa: F401
