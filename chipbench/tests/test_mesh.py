"""A four-chip write cell of ``cesm-atm-3d`` on four virtual CPU devices
(``BENCHMARK.json`` holds none yet): a whole run at a small size comes
out correct, each fault it can have comes out not correct,
the sharded generator is the unsharded one placed on the mesh, and the
reference reads the sharded driver's files as it reads the one-chip
driver's.  Each case runs in a process of its own (``_mesh.py``); they
start together."""
import os
import subprocess
import sys
import json

import pytest

from chipbench import harness, loadgen
from chipbench.tests import _mesh

FAULTS = ["state_unchanged", "half_left_out", "exchange_left_out",
          "centers_altered"]


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    procs = {c: subprocess.Popen(
        [sys.executable, "-m", "chipbench.tests._mesh", c],
        cwd=harness.ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for c in _mesh.CASES}
    out = {}
    for c, p in procs.items():
        stdout, stderr = p.communicate(timeout=900)
        out[c] = (json.loads(stdout.strip().splitlines()[-1])
                  if p.returncode == 0 else stderr[-4000:])
    return out


def _result(cases, name) -> dict:
    got = cases[name]
    assert isinstance(got, dict), got
    return got


@pytest.mark.parametrize("case", ["correct", "correct_straddle"])
def test_the_cell_is_correct_at_a_small_size(cases, case):
    out = _result(cases, case)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert out["device"]["count"] == 4


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_comes_out_not_correct(cases, fault):
    assert not _result(cases, fault)["correct"]


def test_control_fails_and_the_reference_itself_passes(cases):
    out = _result(cases, "control")
    assert any(v["fails"] for v in out["bfloat16"].values()), out
    assert not any(v["fails"] for v in out["float32"].values()), out
    assert out["chips"] == 1 and out["mesh"] == out["bfloat16"]


def test_sharded_generator_places_the_unsharded_values(cases):
    out = _result(cases, "generator")
    assert out["spec"] == "PartitionSpec(None, 'data', None)"
    assert out["shards"] == [[8, 16, 64]] * 4
    assert out["same_run_to_run"] and out["same_as_unsharded"]
    want, got = out["host"], out["device"]
    assert got["jump"] == pytest.approx(want["jump"], rel=0.3)
    assert got["static"] == pytest.approx(want["static"], rel=0.1)
    assert got["spread"] == pytest.approx(want["spread"], rel=0.1)


def test_reference_reads_sharded_files_as_one_chip_files(cases):
    out = _result(cases, "files")
    assert out["steps"] == 4 and out["identical"], out


def test_make_driver_refuses_an_unknown_driver():
    config = dict(harness.load_config("cesm-atm-3d"), driver="NoSuchDriver")
    with pytest.raises(ValueError, match="unknown driver 'NoSuchDriver'"):
        loadgen.make_driver(config, [])
