"""Device time by program, and idle time by the program's own spans.

Every device program of the plane has a stable name (its trace module is
`jit_plane_append`, `jit_plane_major`, ...), and every kept span of the
program's tracer is also a profiler annotation on the host (`ingest.encode`,
`ingest.append`, `ingest.major`, ...). This module reads both out of a
device trace, beside trace.py's busy time and breakdown:

    {"devices": ..., "host": ...,        # as trace.load gives them
     "modules": {plane name: [[start_ns, end_ns, module name], ...]}}

`programs` gives each program's device seconds in the window and its runs;
`idle_gaps_by_span` files every idle gap of the first device under the
program span that overlaps it most.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from . import trace as tracemod

MODULES_LINE = "XLA Modules"  # one event per program run on the device
SPAN_PREFIXES = ("ingest.", "query.", "serve.")  # the program's own spans
_JIT = re.compile(r"jit_(\w+)")


def load(log_dir: str) -> Dict:
    """trace.load's plain lists of the newest trace under log_dir, with each
    device plane's module runs."""
    from jax.profiler import ProfileData

    out = tracemod.load(log_dir)
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out["modules"] = {
        plane.name: [[int(e.start_ns), int(e.start_ns + e.duration_ns), e.name]
                     for line in plane.lines if line.name == MODULES_LINE for e in line.events]
        for plane in pd.planes if plane.name.startswith(tracemod.DEVICE_PREFIX)
    }
    return out


def program_name(module: str) -> str:
    """`jit_plane_append(1234)` -> `plane_append`; other modules as named."""
    m = _JIT.match(module)
    return m.group(1) if m else module


def programs(trace: Dict, win: Optional[Tuple[int, int]] = None) -> Dict[str, Dict[str, float]]:
    """{program: {"s": device seconds in the window, "runs": runs that
    overlap it}}, each the mean over the devices that ran any program."""
    win = win or tracemod.window_of(trace)
    mods = {k: v for k, v in trace.get("modules", {}).items() if v}
    if win is None or not mods:
        return {}
    lo, hi = win
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: {"s": 0.0, "runs": 0.0})
    for evs in mods.values():
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                p = out[program_name(name)]
                p["s"] += d / 1e9 / len(mods)
                p["runs"] += 1 / len(mods)
    return dict(out)


def _rank(name: str) -> int:
    """Which host event names a gap: the program's spans first, then the
    benchmark's annotations, then anything the host did."""
    if name.startswith(SPAN_PREFIXES):
        return 2
    return 1 if name.startswith("bench.") else 0


def idle_gaps_by_span(trace: Dict, win: Optional[Tuple[int, int]] = None,
                      top: int = 10) -> List[List]:
    """Every idle gap of the first device in the window, filed under the
    program span (ingest.*, query.*, serve.*) that overlaps it most, the
    innermost on a tie; a gap no program span overlaps goes where trace.py's
    idle_gaps puts it (the benchmark's annotation, else the host event that
    overlaps it most, else "host idle"). [[label, seconds], ...], longest
    first, the `top` longest."""
    win = win or tracemod.window_of(trace)
    devices = {k: v for k, v in trace["devices"].items() if v}
    if win is None or not devices:
        return []
    lo, hi = win
    merged = tracemod._union([(s, e) for s, e, _ in devices[sorted(devices)[0]]], lo, hi)
    edges = [lo] + [x for se in merged for x in se] + [hi]
    gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
    events = sorted(
        (s, e, _rank(name), name if name.startswith(SPAN_PREFIXES + ("bench.",)) else f"{th}:{name}")
        for s, e, name, th in trace["host"]
        if e > lo and s < hi and name != tracemod.WINDOW
    )
    by: Dict[str, float] = defaultdict(float)
    active: List[Tuple[int, int, int, str]] = []
    i = 0
    for gs, ge in gaps:  # both in start order: one pass over the events
        while i < len(events) and events[i][0] < ge:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] > gs]
        best, label = None, "host idle"
        for s, e, rank, name in active:
            key = (rank, min(e, ge) - max(s, gs), s - e)  # the innermost wins a tie
            if key[1] > 0 and (best is None or key > best):
                best, label = key, name
        by[label] += (ge - gs) / 1e9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]
