"""What every script of the chip benchmark does before it runs a cell:
find the chips, put the program's src/ on the path, resolve the cell,
turn on the persistent compile cache, and build the mesh."""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]


class NoChip(RuntimeError):
    """No TPU, an unknown kind of TPU, or fewer chips than the cell asks for."""


@dataclass
class Opened:
    cell: object  # harness.Cell
    mesh: object
    kind: str
    hbm_bytes: float
    cache_dir: str


def open_cell(workload: str) -> Opened:
    """The cell on this machine's chips. Raises NoChip where they are not
    there, and harness.BenchError where the cell's files are not."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r}); nothing was run")
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import use_compile_cache
    from repro.launch.mesh import make_dev_mesh

    from . import harness

    cell = harness.resolve(workload)
    if len(devices) < cell.chips:
        raise NoChip(f"{workload} needs {cell.chips} chips, found {len(devices)}")
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return Opened(cell, make_dev_mesh(cell.chips, 1), kind, float(peaks[kind]["hbm_bytes"]), cache)
