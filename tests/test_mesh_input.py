"""ShardedCompressor takes a field where the mesh holds it.

A jax.Array on the compressor's mesh, in any sharding, is re-laid out on
the mesh and its exception values are gathered there; the NCK files are
byte-identical to those of the same field handed over as a NumPy array.

Runs in a subprocess with four virtual CPU devices so the main pytest
process keeps seeing exactly one device.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import hashlib, json, os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import NumarckParams, TemporalCompressor
    from repro.core.compress import decompress_step
    from repro.core.container import NCKWriter
    from repro.distributed.pipeline import ShardedCompressor
    from repro.obs import telemetry

    assert len(jax.devices()) == 4
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.default_rng(17)
    shape = (8, 64, 64)
    series = [rng.normal(2.0, 0.5, shape).astype(np.float32)]
    for t in range(4):
        c = (series[-1] * (1 + 0.01 * rng.standard_normal(shape))
             ).astype(np.float32)
        c.reshape(-1)[t::97] *= 30.0        # exceptions on every shard
        c.reshape(-1)[3 + t] = -0.0          # an exception that is -0.0
        series.append(c)
    # 5120-element index blocks straddle the 8192-element shards.
    params = NumarckParams(error_bound=1e-3, block_bytes=3200)
    layouts = {"band": NamedSharding(mesh, P(None, "data", None)),
               "replicated": NamedSharding(mesh, P())}

    def nck(step):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "s.nck")
            w = NCKWriter()
            w.add_step("s", step)
            w.write(path)
            with open(path, "rb") as f:
                return f.read()

    def run(kind):
        if kind == "temporal":
            comp = TemporalCompressor(params)
        else:
            comp = ShardedCompressor(mesh, "data", params)
        steps, counts, names = [], [], set()
        for a in series:
            x = a if kind in ("numpy", "temporal") else jax.device_put(
                a, layouts[kind])
            with telemetry.capture() as reg:
                steps.append(comp.add(x))
            snap = reg.snapshot()
            counts.append(snap["counters"].get("encode.input_host_bytes"))
            names |= {s.name for s in snap["spans"]}
        comp.close()
        recon, prev = [], None
        for s in steps:
            prev = (series[0] if prev is None
                    else decompress_step(s, prev).reshape(shape))
            recon.append(hashlib.sha256(np.ascontiguousarray(prev)
                                        ).hexdigest())
        return {"files": [hashlib.sha256(nck(s)).hexdigest()
                          for s in steps],
                "recon": recon, "host_bytes": counts,
                "relayout": "encode.relayout" in names,
                "exceptions": [int(s.n_incompressible) for s in steps],
                "dtype": [str(s.dtype) for s in steps],
                "shape": [list(s.shape) for s in steps]}

    out = {k: run(k) for k in ("numpy", "band", "replicated", "temporal")}

    # Exception values gathered on the mesh against the host gather at the
    # same positions.
    sc = ShardedCompressor(mesh, "data", params)
    dev = sc._device_encode(series[0],
                            jax.device_put(series[1], layouts["band"]))
    enc = dev.enc
    host = series[1].reshape(-1)[enc.exc_positions]
    out["gather"] = {
        "k": int(enc.exc_positions.size),
        "equal_bits": bool(np.array_equal(enc.exc_values.view(np.int32),
                                          host.view(np.int32))),
        "dtype": str(enc.exc_values.dtype)}
    # A host input leaves the values to finalize.
    out["gather_host_route"] = sc._device_encode(
        series[0], series[1]).enc.exc_values is None
    sc.close()

    # compress_async: both inputs on the mesh against both on the host.
    sc = ShardedCompressor(mesh, "data", params, overlap=True)
    a = sc.compress(jax.device_put(series[1], layouts["band"]),
                    jax.device_put(series[2], layouts["replicated"]))
    b = sc.compress(series[1], series[2])
    sc.close()
    out["compress_pair_equal"] = nck(a) == nck(b)
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-4000:]
    line = [x for x in res.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("layout", ["band", "replicated"])
def test_mesh_layouts_write_the_numpy_files(result, layout):
    assert result[layout]["files"] == result["numpy"]["files"]
    assert result[layout]["recon"] == result["numpy"]["recon"]
    assert result[layout]["dtype"] == ["float32"] * 5
    assert result[layout]["shape"] == [[8, 64, 64]] * 5


def test_the_series_has_exceptions_on_every_delta(result):
    assert all(k > 0 for k in result["numpy"]["exceptions"][1:])


def test_temporal_compressor_writes_the_same_files(result):
    assert result["temporal"]["files"] == result["numpy"]["files"]
    assert result["temporal"]["host_bytes"] == [None] * 5


def test_exception_values_gathered_on_the_mesh_match_the_host(result):
    g = result["gather"]
    assert g["k"] > 0 and g["equal_bits"] and g["dtype"] == "float32"
    assert result["gather_host_route"]


@pytest.mark.parametrize("layout", ["band", "replicated"])
def test_mesh_route_opens_relayout_and_copies_no_delta(result, layout):
    r = result[layout]
    assert r["relayout"]
    n_bytes = 8 * 64 * 64 * 4
    # The anchor's lossless host encode fetches it once; deltas never.
    assert r["host_bytes"] == [n_bytes] + [0] * 4


def test_host_route_counts_every_step(result):
    r = result["numpy"]
    assert not r["relayout"]
    assert r["host_bytes"] == [8 * 64 * 64 * 4] * 5


def test_compress_takes_mesh_inputs(result):
    assert result["compress_pair_equal"]
