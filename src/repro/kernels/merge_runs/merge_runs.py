"""Pallas TPU kernel: k-way sorted-run merge rank computation.

Major compaction merges the LSM tablet's sorted runs into one run
(tables.py host tablets, dist_ingest.py device tablets). The previous
placeholder concatenated and re-sorted — O(n log n) comparison sort that
ignores the input's sortedness. This kernel computes, for every element,
its final position in the merged output directly:

    rank(x in run j) = index of x within run j
                     + sum over runs i < j of |{y in run i : y <= x}|
                     + sum over runs i > j of |{y in run i : y <  x}|

The <=/< split is the stable tie-break (earlier runs win), which makes the
ranks a permutation of [0, K*R) even with duplicate keys — the scatter
epilogue in ops.py then places keys and payload columns in one pass.

Keys are (hi, lo) int32 lanes (64-bit packed host keys never touch 64-bit
device lanes — same convention as merge_intersect; 32-bit device keys pass
hi=0). Runs are padded to a power-of-two length R with +INF sentinels
(hi=INT32_MAX, lo=unsigned max), which sort after every real key, so the
merged output carries its sentinels as a contiguous tail.

Each count is a branchless binary-search descent over one run: log2(R)
fori steps plus one final adjust, vectorized across a (BLOCK,) element
tile; the full (K, R) key lanes stay VMEM-resident across the grid
(ops.py enforces the documented VMEM cap and raises beyond it). Work per element is K*log2(R) compares vs log2(K*R)
full data movements for the sort — and the payload columns never enter
the kernel at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

BLOCK = 1024
SIGN = -0x80000000  # int32 sign bit, as a weak-typed Python literal


def _as_unsigned_order(lo):
    """Order-preserving signed image of a uint32 bit pattern:
    u(a) < u(b)  <=>  (a ^ SIGN) < (b ^ SIGN)."""
    return lo ^ SIGN


def _count_rank(b_hi, b_lo, x_hi, x_lo, tie_wins, r: int):
    """Per-element count of run entries ordered before x.

    b_* (R,) sorted ascending by (hi, lo-unsigned); x_* (BLOCK,) probe
    keys; tie_wins: scalar bool — equal keys in this run count as before x
    (the stable earlier-run-wins tie-break). Branchless descent: after
    log2(R) halving steps plus one final adjust, pos = the count."""
    n_steps = max(r.bit_length() - 1, 0)  # r is a power of two
    pos = jnp.zeros(x_hi.shape, jnp.int32)

    def before(cand):
        ch = jnp.take(b_hi, cand, axis=0)
        cl = jnp.take(b_lo, cand, axis=0)
        lt = (ch < x_hi) | ((ch == x_hi) & (cl < x_lo))
        eq = (ch == x_hi) & (cl == x_lo)
        return lt | (eq & tie_wins)

    def step(s, pos):
        half = jnp.int32(r) >> (s + 1)
        return jnp.where(before(pos + half - 1), pos + half, pos)

    pos = lax.fori_loop(0, n_steps, step, pos)
    return pos + before(pos).astype(jnp.int32)


def _kernel(tile_hi_ref, tile_lo_ref, runs_hi_ref, runs_lo_ref, rank_ref, *, k: int, r: int, block: int):
    j = pl.program_id(0)  # which run this tile belongs to
    tb = pl.program_id(1)  # tile index within the run
    x_hi = tile_hi_ref[0, :]
    x_lo = _as_unsigned_order(tile_lo_ref[0, :])
    # Own index within run j (duplicates within a run stay in order).
    own = tb * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0).reshape(block)
    rank = own
    for i in range(k):  # static unroll: K is small (max_runs + 1)
        b_hi = runs_hi_ref[i, :]
        b_lo = _as_unsigned_order(runs_lo_ref[i, :])
        tie_wins = jnp.int32(i) < j
        cnt = _count_rank(b_hi, b_lo, x_hi, x_lo, tie_wins, r)
        rank = rank + jnp.where(jnp.int32(i) == j, 0, cnt)
    rank_ref[0, :] = rank


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def merge_ranks_pallas(runs_hi, runs_lo, *, interpret: bool = True, block: int = BLOCK):
    """runs_* (K, R) int32 lanes, each row sorted ascending by
    (hi, lo-unsigned) and +INF-sentinel padded; R a power of two with
    R % block == 0 (or R == block after clamping in ops.py). Returns
    int32 (K, R) output ranks — a permutation of [0, K*R)."""
    k, r = runs_hi.shape
    block = min(block, r)
    assert r % block == 0, (r, block)
    grid = (k, r // block)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, r=r, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block), lambda j, b: (j, b)),
            pl.BlockSpec((1, block), lambda j, b: (j, b)),
            pl.BlockSpec((k, r), lambda j, b: (0, 0)),
            pl.BlockSpec((k, r), lambda j, b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda j, b: (j, b)),
        out_shape=jax.ShapeDtypeStruct((k, r), jnp.int32),
        interpret=interpret,
    )(runs_hi, runs_lo, runs_hi, runs_lo)
