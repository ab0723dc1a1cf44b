"""Span tracing with parent linkage and device-time fencing.

Spans are cheap context managers::

    with span("query.step", cat="query", session=sid) as sp:
        out = step_fn(...)
        sp.fence(out)          # block_until_ready; accrues device time
        sp.set(rows=int(n))    # attach results post-hoc

Tracing is OFF by default. When disabled, :func:`span` returns a shared
singleton whose ``__enter__``/``__exit__``/``fence``/``set`` are no-ops —
the total disabled cost is one global load, one attribute check, and a
function call, which the overhead gate in tests/test_obs.py bounds at
< 2% of a scan microbench step.

Parent linkage is thread-local: the innermost open span on the current
thread is the parent of the next one opened. Records accumulate in a
bounded deque and export to Chrome trace-event JSON via
repro.obs.export.chrome_trace (loadable in Perfetto).

SAMPLING: ``enable(sample=1/N)`` keeps every Nth ROOT span (per-process
deterministic counter) and drops the rest; children always follow their
root's fate, so sampled traces contain only complete trees — never a
child whose parent is missing. Sampled-out spans cost one thread-local
read and return a no-op singleton whose ``fence`` passes values through
WITHOUT blocking (same contract as disabled tracing), keeping always-on
tracing affordable under sustained serve-plane load.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from .flight import get_flight

__all__ = [
    "Tracer",
    "clear",
    "disable",
    "enable",
    "enabled",
    "get_tracer",
    "span",
]


class _NullSpan:
    """Singleton returned while tracing is disabled; every verb no-ops."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: object) -> None:
        return None

    def fence(self, x: object) -> object:
        return x

    def set(self, **kw: object) -> None:
        return None


_NULL = _NullSpan()


class _DropSpan:
    """Returned for sampled-out spans. Tracks a thread-local drop depth so
    every span opened UNDER a dropped root is dropped too (a sampled
    trace never contains an orphaned child). fence() passes through
    without blocking, like the disabled-tracing singleton."""

    __slots__ = ("tracer",)

    def __init__(self, tracer: "Tracer") -> None:
        self.tracer = tracer

    def __enter__(self) -> "_DropSpan":
        tls = self.tracer._tls
        tls.drop_depth = getattr(tls, "drop_depth", 0) + 1
        return self

    def __exit__(self, *exc: object) -> None:
        self.tracer._tls.drop_depth -= 1

    def fence(self, x: object) -> object:
        return x

    def set(self, **kw: object) -> None:
        return None


class _Span:
    """A kept span. Besides its record, it opens a
    ``jax.profiler.TraceAnnotation`` of its name on the same thread, so a
    profiler trace shows the program's spans on the device trace's clock
    (a no-op while no profiler trace is being collected)."""

    __slots__ = ("tracer", "name", "cat", "args", "sid", "parent", "tid", "t0", "fence_s", "ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict[str, Any]) -> None:
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.sid = 0
        self.parent = 0
        self.tid = 0
        self.t0 = 0.0
        self.fence_s = 0.0
        self.ann = None

    def __enter__(self) -> "_Span":
        import jax

        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        tr = self.tracer
        self.sid = tr._next_sid()
        stack = tr._stack()
        self.parent = stack[-1].sid if stack else 0
        self.tid = threading.get_ident()
        tr._note_thread(self.tid)
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: object) -> None:
        t1 = time.perf_counter()
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self.tracer._record(self, t1 - self.t0)
        self.ann.__exit__(None, None, None)

    def fence(self, x: object) -> object:
        """Block until a jax value is ready; the wait is charged to this
        span as device time. Works on pytrees; passes through non-jax
        values untouched. A device error raises here, at the fence."""
        import jax

        t0 = time.perf_counter()
        jax.block_until_ready(x)
        self.fence_s += time.perf_counter() - t0
        return x

    def set(self, **kw: object) -> None:
        self.args.update(kw)


class Tracer:
    def __init__(self, maxlen: int = 65536) -> None:
        self.enabled = False
        self.sample_n = 1  # keep every Nth root span (1 = keep all)
        self.records: Deque[Dict[str, Any]] = deque(maxlen=maxlen)
        self.epoch = time.perf_counter()
        self._sid = 0
        self._root_count = 0
        self._sid_lock = threading.Lock()
        self._tls = threading.local()
        self._threads: Dict[int, str] = {}
        self._threads_lock = threading.Lock()
        self._drop = _DropSpan(self)
        self._flight = get_flight()

    # -- internals -------------------------------------------------------
    def _next_sid(self) -> int:
        with self._sid_lock:
            self._sid += 1
            return self._sid

    def set_sample(self, sample: Optional[float]) -> None:
        """sample = fraction of root spans to keep (1/N); None or >= 1
        keeps everything. Resets the root counter, so every enable()
        starts a fresh deterministic period (the first root is always
        kept) and tests can assert exactly which roots survive."""
        with self._sid_lock:
            self._root_count = 0
        if sample is None or sample >= 1:
            self.sample_n = 1
        elif sample <= 0:
            raise ValueError(f"sample must be in (0, 1]: {sample}")
        else:
            self.sample_n = max(1, int(round(1.0 / sample)))

    def _sample_root(self) -> bool:
        with self._sid_lock:
            self._root_count += 1
            return self._root_count % self.sample_n == 1

    def _stack(self) -> List[_Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = []
            self._tls.stack = st
        return st

    def _note_thread(self, tid: int) -> None:
        if tid not in self._threads:
            with self._threads_lock:
                self._threads[tid] = threading.current_thread().name

    def _record(self, sp: _Span, dur: float) -> None:
        rec = {
            "name": sp.name,
            "cat": sp.cat,
            "sid": sp.sid,
            "parent": sp.parent,
            "tid": sp.tid,
            "t0": sp.t0 - self.epoch,
            "dur": dur,
            "args": sp.args,
        }
        if sp.fence_s:
            rec["fence_s"] = sp.fence_s
        self.records.append(rec)
        # Forward every kept record to the flight recorder (its window
        # stays continuous whether tracing is on or off); sids share a
        # namespace inside a dump — flight-native sids start far above
        # the tracer counter, so linkage never collides.
        fr = self._flight
        if fr.enabled:
            fr.record(
                sp.name, sp.cat, sp.sid, sp.parent, sp.tid,
                sp.t0, dur, sp.fence_s, sp.args,
            )

    # -- public ----------------------------------------------------------
    def span(self, name: str, cat: str = "", **args: object):
        if not self.enabled:
            return _NULL
        if self.sample_n > 1:
            if getattr(self._tls, "drop_depth", 0) > 0:
                return self._dropped(name, cat, args)  # child of dropped root
            if not self._stack() and not self._sample_root():
                return self._dropped(name, cat, args)  # root not sampled
        return _Span(self, name, cat, dict(args))

    def _dropped(self, name: str, cat: str, args: Dict[str, Any]):
        """A span the sampler rejects: normally the cheap drop singleton,
        but when the flight recorder is on it records there anyway — the
        flight window is bounded by TIME, not rate, so sampling must not
        punch holes in it. The flight span maintains the tracer's
        drop-depth exactly like the singleton, so children still follow
        their root's fate in the sampled trace."""
        fr = self._flight
        if fr.enabled:
            return fr.span(name, cat, dict(args) if args else None, drop_tls=self._tls)
        return self._drop

    def add_complete(
        self,
        name: str,
        t0: float,
        dur: float,
        cat: str = "",
        tid: Optional[int] = None,
        **args: object,
    ) -> None:
        """Record a span retroactively from (start, duration) timestamps
        measured elsewhere — used for lock-hold segments, which are timed
        by OwnedLock whether or not tracing was on when they began. The
        flight recorder receives these too (when enabled), so incident
        dumps carry lock tracks even with tracing off."""
        fr = self._flight
        if fr.enabled:
            fr.record_complete(
                name, cat, tid if tid is not None else threading.get_ident(),
                t0, dur, dict(args),
            )
        if not self.enabled:
            return
        if tid is None:
            tid = threading.get_ident()
        self._note_thread(tid)
        self.records.append(
            {
                "name": name,
                "cat": cat,
                "sid": self._next_sid(),
                "parent": 0,
                "tid": tid,
                "t0": t0 - self.epoch,
                "dur": dur,
                "args": dict(args),
            }
        )

    def clear(self) -> None:
        self.records.clear()
        self.epoch = time.perf_counter()

    def thread_names(self) -> Dict[int, str]:
        with self._threads_lock:
            return dict(self._threads)


_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def span(name: str, cat: str = "", **args: object):
    """Open a span on the global tracer (no-op singleton when disabled;
    drop singleton when sampled out — see module docstring). When the
    FLIGHT RECORDER is enabled, a disabled tracer yields a recording
    flight span instead of the null singleton: the last-N-seconds window
    exists whether or not anyone turned tracing on."""
    if not _tracer.enabled:
        fr = _tracer._flight
        if fr.enabled:
            return fr.span(name, cat, dict(args) if args else None)
        return _NULL
    return _tracer.span(name, cat, **args)


def enable(sample: Optional[float] = None) -> None:
    """Turn tracing on. ``sample=1/N`` keeps every Nth root span (children
    follow their root); omitted or >= 1 keeps everything."""
    _tracer.set_sample(sample)
    _tracer.enabled = True


def disable() -> None:
    _tracer.enabled = False
    _tracer.set_sample(None)


def enabled() -> bool:
    return _tracer.enabled


def clear() -> None:
    _tracer.clear()
