"""The plain numpy reference every device result is compared with.

Copied from chip_smoke.py (Reference, _fingerprints, _contains and the
writers' placement replay) at commit a706375d2ec3ae046a9a10ad90b9f70ad495ba7d,
with two changes: a check returns the faults it found instead of raising
on the first, and the row hash (src/repro/core/keypack.py::short_hash at
the same commit) is copied here, so the reference imports nothing of the
program.

Semantics: Eq filters over inclusive time ranges; per-tablet newest-k
delivery (BatchScanner semantics); count per (group, time bucket); and
the placement of each acknowledged row in the tablet its writer's row
hash names.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

TS_MAX = (1 << 30) - 1  # keypack.TS_BITS = 30
HASH_MAX = (1 << 16) - 1  # keypack.HASH_BITS = 16


def short_hash(*cols) -> np.ndarray:
    """16-bit fnv-style mixing hash over int arrays (the writers' row hash)."""
    acc = np.uint64(0xCBF29CE484222325)
    for c in cols:
        c = np.asarray(c).astype(np.uint64)
        acc = (acc ^ c) * np.uint64(0x100000001B3)
        acc ^= acc >> np.uint64(29)
    return (acc & np.uint64(HASH_MAX)).astype(np.int64)


def writer_tablets(ts, codes, nonce, writer_id, n_tablets: int) -> np.ndarray:
    """Tablet of each row a DistBatchWriter wrote: the row hash of its
    encoded fields, its timestamp, the writer's running row count and the
    writer's id, modulo the tablet count."""
    h = short_hash(*(codes[:, j] for j in range(codes.shape[1])), ts, nonce, writer_id)
    return (h % n_tablets).astype(np.int64)


def fingerprints(ts: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """One uint64 per (ts, 12 codes) row, for multiset comparisons."""
    acc = np.asarray(ts).astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    for j in range(codes.shape[1]):
        acc = (acc ^ codes[:, j].astype(np.uint64)) * np.uint64(0x100000001B3)
        acc ^= acc >> np.uint64(31)
    return acc


def contains(big: np.ndarray, small: np.ndarray) -> bool:
    """Multiset containment small <= big."""
    bu, bc = np.unique(big, return_counts=True)
    su, sc = np.unique(small, return_counts=True)
    pos = np.searchsorted(bu, su)
    if np.any(pos >= bu.size):
        return False
    return bool(np.all(bu[pos] == su) and np.all(bc[pos] >= sc))


class Reference:
    """Rows (ts sorted ascending, codes, tablet) and the answers the
    device must give over them."""

    def __init__(self, ts, codes, tablet, n_tablets: int, top_k: int, field_ids: Dict[str, int]):
        self.ts = ts
        self.codes = codes
        self.tablet = tablet
        self.n_tablets = n_tablets
        self.top_k = top_k
        self.field_ids = field_ids
        self.fp = fingerprints(ts, codes)

    def matching(self, field: str, code: int) -> np.ndarray:
        """Indices of rows with field == code, in ts order."""
        return np.flatnonzero(self.codes[:, self.field_ids[field]] == code)

    def tablet_rows(self) -> np.ndarray:
        return np.bincount(self.tablet, minlength=self.n_tablets)

    def check_batch(self, rows: np.ndarray, rb) -> List[str]:
        """One delivered batch: exact count; the delivered ts multiset is
        each tablet's k newest; every delivered row is a real match; every
        row newer than its tablet's k-th newest was delivered."""
        lo, hi = int(rb.lo), int(rb.hi)
        ts_q = self.ts[rows]
        sel = rows[np.searchsorted(ts_q, lo, "left"):np.searchsorted(ts_q, hi, "right")]
        faults = []
        if rb.count != sel.size:
            faults.append(f"batch [{lo},{hi}] count {rb.count} != {sel.size}")
        want_ts, sure = [], []
        tab = self.tablet[sel]
        for t in range(self.n_tablets):
            r_t = sel[tab == t]
            top = r_t[-self.top_k:]
            want_ts.append(self.ts[top])
            if r_t.size > self.top_k:
                sure.append(top[self.ts[top] > self.ts[top[0]]])
            else:
                sure.append(top)
        got_ts = np.asarray(rb.ts, np.int64)
        if not np.array_equal(np.sort(got_ts), np.sort(np.concatenate(want_ts))):
            faults.append(f"batch [{lo},{hi}] delivered ts multiset differs")
        got_fp = fingerprints(got_ts, np.asarray(rb.cols))
        if not contains(self.fp[sel], got_fp):
            faults.append(f"batch [{lo},{hi}] delivered a non-matching row")
        if not contains(got_fp, self.fp[np.concatenate(sure)]):
            faults.append(f"batch [{lo},{hi}] missed a row inside the newest-{self.top_k} cut")
        return faults

    def check_stream(self, rows: np.ndarray, batches: Sequence, t0: int, t1: int) -> Tuple[int, List[str]]:
        """All batches of one query: each exact, and together they hold
        every matching row of [t0, t1]. Returns (wrong batches, faults)."""
        wrong, faults = 0, []
        for rb in batches:
            f = self.check_batch(rows, rb)
            wrong += bool(f)
            faults += f
        total = sum(int(rb.count) for rb in batches)
        want = int(np.count_nonzero((self.ts[rows] >= t0) & (self.ts[rows] <= t1)))
        if total != want:
            faults.append(f"query total {total} != {want}")
        return wrong, faults

    def count_per(self, field: str, bucket_s: int, t0: int, t1: int) -> Dict[Tuple[int, int], int]:
        """{(code, bucket start ts): rows} over [t0, t1]."""
        m = (self.ts >= t0) & (self.ts <= t1)
        codes = self.codes[m, self.field_ids[field]].astype(np.int64)
        buckets = self.ts[m] // bucket_s
        keys, counts = np.unique(codes * (1 << 32) + buckets, return_counts=True)
        return {(int(k >> 32), int(k & 0xFFFFFFFF) * bucket_s): int(c) for k, c in zip(keys, counts)}


def aggregate_faults(want: Dict[Tuple[int, int], int], got: Dict[Tuple[int, int], int]) -> int:
    """Cells of a count-per-group table that differ (missing, extra or wrong)."""
    return sum(want.get(k) != got.get(k) for k in set(want) | set(got))
