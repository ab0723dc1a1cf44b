"""Device trace: record it around the measured window, and reduce it to
busy time, idle share and a breakdown of where the time went.

The profiler writes an .xplane.pb; `load` turns it into plain lists,

    {"devices": {plane name: [[start_ns, end_ns, op name], ...]},
     "host": [[start_ns, end_ns, event name, thread], ...]}

on the trace's own clock, and `reduce` works on that form alone, so a
small recorded trace in the same form tests it. The window is the span
of the benchmark's own `bench.window` annotation.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:"
OPS_LINES = ("XLA Ops",)  # one event per operation run on the device
ATTRIBUTED_GAPS = 200  # the longest idle gaps, each named by the host's activity


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no Python function events: they slow the host
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(log_dir: str) -> Dict:
    """The newest .xplane.pb under log_dir, as plain lists."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name in OPS_LINES:
                    evs += [[int(e.start_ns), int(e.start_ns + e.duration_ns), e.name]
                            for e in line.events]
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[int(e.start_ns), int(e.start_ns + e.duration_ns), e.name, line.name]
                         for e in line.events if e.duration_ns > 0]
    return {"devices": devices, "host": host}


def _union(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged, clipped [start, end) intervals inside [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(trace: Dict) -> Optional[Tuple[int, int]]:
    spans = [(s, e) for s, e, name, _ in trace["host"] if name == WINDOW]
    return max(spans, key=lambda se: se[1] - se[0]) if spans else None


class _Host:
    """Host events as arrays, to find what overlaps a gap."""

    def __init__(self, host: Sequence):
        rows = [h for h in host if h[2] != WINDOW]
        self.s = np.asarray([h[0] for h in rows], np.int64)
        self.e = np.asarray([h[1] for h in rows], np.int64)
        self.ours = np.asarray([h[2].startswith("bench.") for h in rows], bool)
        self.label = [h[2] if h[2].startswith("bench.") else f"{h[3]}:{h[2]}" for h in rows]

    def activity(self, gs: int, ge: int) -> str:
        """The benchmark annotation that overlaps [gs, ge] most, else the
        host event that does, else "host idle"."""
        if not self.s.size:
            return "host idle"
        ov = np.minimum(self.e, ge) - np.maximum(self.s, gs)
        for pick in (ov * self.ours, ov * ~self.ours):
            i = int(np.argmax(pick))
            if pick[i] > 0:
                return self.label[i]
        return "host idle"


def reduce(trace: Dict, top: int = 10) -> Optional[Dict]:
    """busy_s (union of device-op intervals, mean over devices), window_s,
    idle_share, and the breakdown: the device ops that took most time
    (seconds per device) and the first device's idle time by what the
    host was doing (its longest gaps one by one, the rest together).
    None when the trace holds no window or no device op in it."""
    win = window_of(trace)
    devices = {k: v for k, v in trace["devices"].items() if v}
    if win is None or not devices:
        return None
    lo, hi = win
    busy_ns = []
    op_s: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    host = _Host([h for h in trace["host"] if h[1] > lo and h[0] < hi])
    gaps: List[Tuple[int, int]] = []
    for i, (_, evs) in enumerate(sorted(devices.items())):
        merged = _union([(s, e) for s, e, _ in evs], lo, hi)
        busy_ns.append(sum(e - s for s, e in merged))
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_s[name] += d / 1e9 / len(devices)
        if i == 0:  # attribute the first device's gaps
            edges = [lo] + [x for se in merged for x in se] + [hi]
            gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
    gaps.sort(key=lambda g: g[0] - g[1])
    for gs, ge in gaps[:ATTRIBUTED_GAPS]:
        idle_by[host.activity(gs, ge)] += (ge - gs) / 1e9
    if len(gaps) > ATTRIBUTED_GAPS:
        idle_by["shorter gaps"] = sum(ge - gs for gs, ge in gaps[ATTRIBUTED_GAPS:]) / 1e9
    if not any(busy_ns):
        return None
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "n_devices": len(devices),
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]],
        },
    }
