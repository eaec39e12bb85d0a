"""The sharded driver takes each field where the mesh holds it: a field
in the cell's latitude-band layout, a replicated one and the same values
as a NumPy array give byte-identical NCK files over an anchor and four
deltas, which the reference decodes to the same reconstructions.  Runs
on four virtual CPU devices in a process of its own."""
import json
import os
import subprocess
import sys
import textwrap

from chipbench import harness

_SCRIPT = textwrap.dedent("""
    import hashlib, json, os, tempfile
    from chipbench.tests import conftest  # noqa: F401  (paths, CPU)
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from chipbench import data, harness, loadgen, reference as ref
    from repro.core.container import NCKWriter

    config = harness.load_config("cesm-atm-3d")
    config["shape"] = [8, 64, 64]
    config["params"] = dict(config["params"], block_bytes=3200)
    devices = jax.devices()
    band = loadgen.field_sharding(config, devices)
    gen = data.FieldGen(config["shape"], "float32", config["stats"], band)
    key = data.seed_key(2**31 + 5)
    pool = [gen.change(key, j) for j in range(2)]
    series = list(gen.series(key, 1, pool, [0, 1, 1, 0]))
    layouts = {"band": lambda a: a,
               "replicated": lambda a: jax.device_put(
                   a, NamedSharding(band.mesh, P())),
               "numpy": lambda a: np.asarray(a)}
    out = {}
    with tempfile.TemporaryDirectory() as d:
        for kind, place in layouts.items():
            comp = loadgen.make_driver(config, devices)
            files, recon = [], []
            got = None
            for t, a in enumerate(series):
                step = comp.add(place(a))
                path = os.path.join(d, f"{kind}{t}.nck")
                w = NCKWriter()
                w.add_step("s", step)
                w.write(path)
                with open(path, "rb") as f:
                    files.append(hashlib.sha256(f.read()).hexdigest())
                got = ref.decode(ref.NCKFile(path), "s", got)[0]
                recon.append(hashlib.sha256(
                    np.ascontiguousarray(got)).hexdigest())
            comp.close()
            out[kind] = {"files": files, "recon": recon}
    print(json.dumps(out))
""")


def test_mesh_layouts_write_and_decode_as_the_numpy_field():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=harness.ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for kind in ("band", "replicated"):
        assert out[kind]["files"] == out["numpy"]["files"], kind
        assert out[kind]["recon"] == out["numpy"]["recon"], kind
    assert len(out["numpy"]["files"]) == 5
    assert len(set(out["numpy"]["recon"])) == 5
