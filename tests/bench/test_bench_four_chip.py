"""The four-chip cell on four virtual CPU devices (a subprocess: the
device count is fixed when JAX starts), driven past the harness's look
for a chip: sound, it comes out correct; with the exchange between chips
left out, `correct` comes out false."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.abspath(os.path.join(HERE, "..", "..", "src"))

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1]]
import conftest  # puts the benchmark's package on the path
from chipbench import faults, harness
from repro.launch.mesh import make_dev_mesh

cell = conftest.tiny("llcysa4.ingest")
mesh = make_dev_mesh(4, 1)
out = {}
for fault in ("none", "exchange_left_out"):
    with faults.plant(fault):
        o = harness.execute(cell, 2**31 + 23, 2.0, False, mesh, time.perf_counter(),
                            log=lambda m: None, work_dir=__import__("pathlib").Path(sys.argv[2]))
    out[fault] = {"correct": o.correct, "checks": o.checks, "count": o.device["count"],
                  "metrics": sorted(o.metrics)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, HERE, str(tmp_path_factory.mktemp("w"))],
                          capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_ingest_sound_run_is_correct(runs):
    r = runs["none"]
    assert r["correct"] and r["count"] == 4
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert {"ingest_rows_per_s", "setup_s"} <= set(r["metrics"])


def test_four_chip_ingest_exchange_left_out_is_not_correct(runs):
    r = runs["exchange_left_out"]
    assert not r["correct"] and r["checks"]["tablet_rows_off"]["value"] > 0
