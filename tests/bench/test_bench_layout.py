"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic mix and metric readers by name, the result line
has the contract's keys, and a run refuses without a TPU."""
import json

import pytest

from chipbench import harness

ROOT = harness.CHECKOUT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


ALL = harness.with_held_out(BENCH)


@pytest.mark.parametrize("cell", [w["name"] for w in ALL["workloads"]])
def test_cell_resolves_its_files_by_name(cell):
    c = harness.resolve(cell, BENCH)
    wl = next(w for w in ALL["workloads"] if w["name"] == cell)
    assert c.chips == wl["chips"] == c.config["chips"]
    assert c.end_to_end and c.per_layer
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    for m in c.per_layer:
        assert m["moves"] in names  # the metric it moves is reported in this cell


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(cfg):
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert set(cfg["reduced"]) <= set(data["reduced"]) <= set(data)
    assert data["guarantees"] and data["assumed"]
    assert data["fill_limit_rows"] < data["chips"] * data["tablets_per_device"] * data["capacity"]


def test_paths_hold_the_command_and_nothing_outside():
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])


def _outcome(traced):
    return harness.Outcome(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
        checks={"overflow": {"value": 0, "limit": 0}},
        breakdown={"device_ops": [["sort", 0.1]], "idle_gaps": [["bench.add", 0.2]]},
        notes={},
    )


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_has_the_contract_keys(traced):
    line = json.loads(harness.result_line(_outcome(traced), traced))
    want = ["correct", "attempted", "failed", "metrics", "device"]
    want += ["breakdown"] if traced else []
    assert list(line) == want + [harness.LIMITS_KEY]  # the compared numbers come last
    assert harness.check_lines(_outcome(traced)) == ["check overflow: 0 (limit 0)"]


_CELL = BENCH["workloads"][0]["name"]


@pytest.mark.parametrize("script,argv", [
    ("run.py", ["--workload", _CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]),
    ("sweep.py", ["--workload", _CELL, "--seed", "1", "--rates", "1", "--seconds", "1"]),
    ("control.py", ["--workload", _CELL, "--seeds", "1", "--seconds", "1"]),
])
def test_refuses_without_a_tpu(capsys, script, argv):
    import importlib.util

    spec = importlib.util.spec_from_file_location(f"bench_{script[:-3]}", harness.BENCH_DIR / script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(argv) != 0
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_unknown_workload_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.resolve("no.such.cell", BENCH)
