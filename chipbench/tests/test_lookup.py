"""Every piece is found by its name, BENCHMARK.json keeps to its own
rules, and a run's last line has the shape the driver reads."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import harness, loadgen
from chipbench.run import end_to_end

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_benchmark()


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_keys_follow_the_rules():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for k in ("configs", "workloads") for x in BENCH[k]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_finds_its_configuration_traffic_and_metrics(cell):
    w = harness.cell_of(BENCH, cell)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert entry["file"] == f"chipbench/configs/{w['config']}.json"
    config = harness.load_config(w["config"])
    assert config["name"] == w["config"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    traffic = harness.load_traffic(w["traffic"])
    assert traffic["mode"] in loadgen.LOADS
    e2e = [m["name"] for m in harness.metrics_for(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    # Every end-to-end metric of the cell is one its mode produces.
    units = [harness.Unit(0.0, 1.0, 100, 10, 5, 0, 1)]
    assert set(e2e) <= set(end_to_end(traffic["mode"], units, 0.0, 1.0))
    per_layer = harness.metrics_for(BENCH, cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in e2e


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        harness.cell_of(BENCH, "no.such.cell")


def test_metric_readers_read_nothing_outside_their_cells():
    ctx = harness.Ctx("read", [], [], [], None, lambda t: t, {}, 1, 4, 0)
    for m in BENCH["per_layer"]:
        assert harness.metric_reader(m["name"])(ctx) is None


def test_last_line_has_the_drivers_keys_and_the_checks_last():
    checks = [harness.Check("mismatch_share", 1e-7, 1e-3)]
    line = harness.result_line(
        True, 12, 0, {"compress_GBps": {"value": 0.03, "unit": "GB/s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 1 << 30}, checks,
        {"device_ops": [["fusion", 0.1]], "idle_gaps": [["finalize", 2.0]]})
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    assert out["checks"] == {"mismatch_share": {"value": 1e-7,
                                                "limit": 1e-3}}


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "isabel.write", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
