"""Public fused combiner op: filter + group-aggregate a sorted run in one
kernel pass.

Input is a run sorted by int64 group key (the CombinerIterator packs group
field codes + time bucket into one key, then sorts). Output is one row per
group that has at least one filter-surviving event: (group key, aggregate,
match count).

Pallas path: tile-local fused kernel + an O(n_tiles) stitch epilogue for
groups straddling tile boundaries. Default ("auto",
kernels/common.py::resolve_backend): the jnp reference on every platform
(identical output, asserted in tests) — the kernel does not compile for
TPU (`dynamic_slice` in the shared program interpreter; ROADMAP D2)."""
from __future__ import annotations

from typing import TYPE_CHECKING, Optional, Tuple

import numpy as np

from ..common import pallas_interpret, resolve_backend, split_key_lanes
from ..filter_scan.ops import LANE, _bucket, _pow2, pad_program
from ..program_eval import OP_PUSH_TRUE
from .combine_scan import BLOCK, OP_MAX, OP_MIN, OP_SUM, combine_scan_pallas
from .ref import combine_scan_ref

if TYPE_CHECKING:  # runtime import would cycle: core/__init__ needs kernels
    from ...core.filter import FilterProgram

OPS = {"count": OP_SUM, "sum": OP_SUM, "min": OP_MIN, "max": OP_MAX}

_SENTINEL32 = np.iinfo(np.int32).max


def trivial_program() -> "FilterProgram":
    """All-rows-match program (combiner with no residual filter)."""
    from ...core.filter import FilterProgram

    return FilterProgram(
        opcodes=np.asarray([OP_PUSH_TRUE], np.int32),
        arg0=np.zeros(1, np.int32),
        arg1=np.zeros(1, np.int32),
        codesets=np.full((1, 1), -1, np.int32),
        max_depth=1,
    )


def _stitch(keys, heads, aggs, cnts, n, op_kind: int) -> None:
    """Fold tile-boundary-straddling groups into their open segment head.
    In-place on the padded arrays; O(n_tiles) host loop."""
    for t in range(1, (len(heads) + BLOCK - 1) // BLOCK):
        i = t * BLOCK
        if i >= n:
            break
        if keys[i] == keys[i - 1]:
            h = i - 1
            while not heads[h]:
                h -= 1
            if op_kind == OP_SUM:
                aggs[h] += aggs[i]
            elif op_kind == OP_MIN:
                aggs[h] = min(aggs[h], aggs[i])
            else:
                aggs[h] = max(aggs[h], aggs[i])
            cnts[h] += cnts[i]
            heads[i] = False


def combine_scan(
    group_keys: np.ndarray,
    values: Optional[np.ndarray],
    cols: np.ndarray,
    prog: Optional[FilterProgram],
    op: str = "count",
    backend: str = "auto",
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fused scan-time aggregation over a sorted run.

    group_keys: int64 (n,) ascending (duplicates = same group).
    values:     int32 (n,) aggregand; ignored for op='count' (may be None).
    cols:       int32 (n, f) dictionary codes — the filter's input.
    prog:       residual FilterProgram, or None for match-all.
    op:         'count' | 'sum' | 'min' | 'max'.

    Returns (unique group keys, aggregates int64, match counts), all
    restricted to groups with count > 0 — filtered-out groups never leave
    the server. Sum/count aggregates accumulate in int64 across tiles and
    blocks; the Pallas kernel's tile-local partials are int32, which is
    exact as long as one BLOCK-row tile cannot wrap (|value| < 2^31/BLOCK
    per row — always true for count, whose values are 1s). An explicit
    backend="pallas" sum over larger values raises ValueError; "auto"
    (the int64 jnp reference) has no such bound.
    """
    op_kind = OPS[op]
    group_keys = np.asarray(group_keys, dtype=np.int64)
    n, f = cols.shape
    assert group_keys.shape == (n,), (group_keys.shape, n)
    if n == 0:
        return (
            np.empty(0, np.int64),
            np.empty(0, np.int64),
            np.empty(0, np.int32),
        )
    if op == "count":
        values = np.ones(n, np.int32)
    values = np.asarray(values, dtype=np.int32)
    if prog is None:
        prog = trivial_program()
    opc, a0, a1, cs = pad_program(prog)
    hi, lo = split_key_lanes(group_keys)
    backend = resolve_backend(backend)
    if (
        backend == "pallas"
        and op == "sum"
        and values.size
        and int(np.abs(values, dtype=np.int64).max()) > (2**31 - 1) // BLOCK
    ):
        raise ValueError(
            "combine_scan pallas: a tile's int32 partial sum could wrap for "
            "values this large; use backend='ref' (int64)"
        )

    if backend == "ref":
        # Pow2-bucket rows to bound retraces (adaptive batching varies n
        # every call). Sentinel-key padding rows may pass a trivial filter,
        # but they form their own trailing segments, dropped by the [:n]
        # slice below.
        n_pad = _pow2(n)
        f_pad = f
    else:
        n_pad = _bucket(n, BLOCK)
        f_pad = _bucket(f, LANE)
    hi_p = np.full(n_pad, _SENTINEL32, np.int32)
    lo_p = np.full(n_pad, _SENTINEL32, np.int32)
    val_p = np.zeros(n_pad, np.int32)
    cols_p = np.full((n_pad, f_pad), -1, np.int32)
    hi_p[:n], lo_p[:n], val_p[:n] = hi, lo, values
    cols_p[:n, :f] = cols

    if backend == "ref":
        heads, aggs, cnts = combine_scan_ref(
            hi_p, lo_p, val_p, cols_p, opc, a0, a1, cs, op_kind=op_kind
        )
        heads = np.asarray(heads)[:n]
        aggs = np.asarray(aggs)[:n]
        cnts = np.asarray(cnts)[:n]
    else:
        heads, aggs, cnts = combine_scan_pallas(
            hi_p, lo_p, val_p, cols_p, opc, a0, a1, cs,
            op_kind=op_kind, interpret=pallas_interpret(),
        )
        heads = np.asarray(heads).copy()
        # Widen before the stitch: cross-tile accumulation must be int64
        # (tile-local int32 partials are bounded by BLOCK rows each).
        aggs = np.asarray(aggs).astype(np.int64)
        cnts = np.asarray(cnts).copy()
        _stitch(group_keys, heads, aggs, cnts, n, op_kind)
        heads = heads[:n]
        aggs = aggs[:n]
        cnts = cnts[:n]

    keep = heads & (cnts > 0)
    return group_keys[keep], np.asarray(aggs[keep], np.int64), cnts[keep]
