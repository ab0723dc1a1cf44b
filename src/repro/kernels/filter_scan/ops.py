"""Public entry point for the filter kernel: padding, program bucketing,
backend dispatch.

Backend policy (kernels/common.py::resolve_backend): "auto" is the jnp
reference on every platform (identical semantics — asserted by the kernel
test suite). The kernel does not compile for TPU (`dynamic_slice` is not
implemented in Mosaic; ROADMAP D2), so it runs only on an explicit
backend="pallas" request — native on TPU, interpret mode elsewhere, as
the tests exercise it."""
from __future__ import annotations

from typing import TYPE_CHECKING, Tuple

import jax.numpy as jnp
import numpy as np

from ..common import pallas_interpret, pow2 as _pow2, resolve_backend
from ..program_eval import OP_NOP

if TYPE_CHECKING:  # runtime import would cycle: core/__init__ needs kernels
    from ...core.filter import FilterProgram
from .filter_scan import BLOCK_ROWS, LANE, filter_scan_pallas
from .ref import filter_scan_ref


def _bucket(n: int, b: int) -> int:
    return max(((n + b - 1) // b) * b, b)


def pad_program(prog: FilterProgram) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad program length to a power of two (bounds retrace count) and the
    codeset table to power-of-two rows/cols."""
    p = _pow2(max(prog.length, 1))
    opc = np.full(p, OP_NOP, np.int32)
    a0 = np.zeros(p, np.int32)
    a1 = np.zeros(p, np.int32)
    opc[: prog.length] = prog.opcodes
    a0[: prog.length] = prog.arg0
    a1[: prog.length] = prog.arg1
    s, m = prog.codesets.shape
    cs = np.full((_pow2(max(s, 1)), _pow2(max(m, 1))), -1, np.int32)
    cs[:s, :m] = prog.codesets
    return opc, a0, a1, cs


def filter_scan(
    cols: np.ndarray,
    prog: FilterProgram,
    backend: str = "auto",
) -> np.ndarray:
    """Evaluate a compiled filter program over a columnar block.

    cols: (n, n_fields) int32 dictionary codes.
    Returns: (n,) bool match mask (numpy).
    """
    n, f = cols.shape
    if n == 0:
        return np.zeros(0, bool)
    opc, a0, a1, cs = pad_program(prog)
    backend = resolve_backend(backend)
    if backend == "ref":
        # Bucket rows to powers of two: adaptive batching produces a
        # different n every call, and per-shape retracing would dominate
        # (measured 100ms+/batch). Padding rows can't match: codes are
        # >= 0, pad is -1.
        n_pad = _pow2(n)
        if n_pad != n:
            cols = np.concatenate([cols, np.full((n_pad - n, f), -1, np.int32)])
        mask = filter_scan_ref(jnp.asarray(cols), opc, a0, a1, cs)
        return np.asarray(mask)[:n]
    # Pallas path: pad rows to the block multiple and fields to the lane.
    n_pad = _bucket(n, BLOCK_ROWS)
    f_pad = _bucket(f, LANE)
    cols_p = np.zeros((n_pad, f_pad), np.int32)
    cols_p[:n, :f] = cols
    mask = filter_scan_pallas(
        jnp.asarray(cols_p), opc, a0, a1, cs, interpret=pallas_interpret()
    )
    return np.asarray(mask)[:n]
