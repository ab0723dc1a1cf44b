"""Faults planted under the timed path, to show that the check catches them.

Each fault is a context manager that patches the program for the
duration of a run. Only the control runs (control.py) and the tests use
them; the benchmark's own runs never do.

  unchanged_state    every other append to the plane returns with the
                     plane's state unchanged (the rows are acknowledged)
  half_batch         each append keeps the first half of its rows only
  altered_answer     the first batch of each query delivers one altered
                     row, or one row more in its count when it has none
  exchange_left_out  a batch reaches the first chip only: rows for tablets
                     on the other chips are acknowledged and dropped
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator

import numpy as np


@contextlib.contextmanager
def _patch(cls, name: str, make: Callable) -> Iterator[None]:
    orig = getattr(cls, name)
    setattr(cls, name, make(orig))
    try:
        yield
    finally:
        setattr(cls, name, orig)


def unchanged_state():
    from repro.core.dist_ingest import DistIngestPlane

    calls = [0]

    def make(orig):
        def ingest(self, rts, cols, tab, writer_id=0):
            calls[0] += 1
            return 0.0 if calls[0] % 2 == 0 else orig(self, rts, cols, tab, writer_id=writer_id)
        return ingest

    return _patch(DistIngestPlane, "ingest", make)


def half_batch():
    from repro.core.dist_ingest import DistIngestPlane

    def make(orig):
        def ingest(self, rts, cols, tab, writer_id=0):
            k = (len(rts) + 1) // 2
            return orig(self, rts[:k], cols[:k], tab[:k], writer_id=writer_id)
        return ingest

    return _patch(DistIngestPlane, "ingest", make)


def exchange_left_out():
    from repro.core.dist_ingest import DistIngestPlane

    def make(orig):
        def ingest(self, rts, cols, tab, writer_id=0):
            k = np.asarray(tab) < self.tablets_per_device
            return orig(self, rts[k], cols[k], tab[k], writer_id=writer_id)
        return ingest

    return _patch(DistIngestPlane, "ingest", make)


def altered_answer():
    from repro.serve_db.session import StreamingQuery

    def make(orig):
        def _deliver(self, rb):
            if self.batches == 0 and rb.cols is not None:
                if len(rb.ts):
                    rb.cols = np.array(rb.cols, copy=True)
                    rb.cols[0, 0] += 1
                else:
                    rb.count += 1
            return orig(self, rb)
        return _deliver

    return _patch(StreamingQuery, "_deliver", make)


FAULTS: Dict[str, Callable] = {
    "unchanged_state": unchanged_state,
    "half_batch": half_batch,
    "altered_answer": altered_answer,
    "exchange_left_out": exchange_left_out,
}


def plant(name: str):
    """The fault's context manager; "none" plants nothing."""
    return contextlib.nullcontext() if name == "none" else FAULTS[name]()
