"""Cases of a four-chip write cell of configuration ``cesm-atm-3d`` on
four virtual CPU devices, one per process: ``python -m
chipbench.tests._mesh <case>`` prints one JSON line.

``BENCHMARK.json`` holds no such cell yet; ``CELL`` is the entry a
benchmark that adds it would give.  The device count has to be set before
JAX starts, so ``test_mesh.py`` runs each case in a process of its own.
"""
import json
import os
import sys

CELL = {"name": "cesm3d.write.4chip", "config": "cesm-atm-3d",
        "traffic": "write", "chips": 4}
# A block of 5120 indices at B = 5: blocks straddle the shards' edges.
STRADDLE = {"block_bytes": 3200}


def _tiny(**params):
    """The cell's configuration and traffic at ``_cells.SHAPE``."""
    from chipbench import harness
    from chipbench.tests._cells import SHAPE
    config = harness.load_config(CELL["config"])
    config["shape"] = SHAPE
    config["params"] = dict(config["params"], **params)
    return config, harness.load_traffic(CELL["traffic"])


def _run(**params) -> dict:
    import time
    import jax
    from chipbench import harness, run
    config, traffic = _tiny(**params)
    return json.loads(run.run_cell(
        harness.load_benchmark(), CELL, 7, 1.0, False, jax.devices()[:4],
        start=time.monotonic(), config=config, traffic=traffic))


def correct(straddle: bool = False) -> dict:
    return _run(**(STRADDLE if straddle else {}))


def state_unchanged() -> dict:
    from repro.distributed import pipeline
    pipeline._ShardedDeviceChain.advance = lambda self, dev, curr: None
    return _run()


def half_left_out() -> dict:
    """Each shard encodes its first half; the second half is encoded as if
    it had not changed since the previous step."""
    from repro.distributed import pipeline
    orig = pipeline._encode_shard

    def half(prev_l, curr_l, *args, **kw):
        h = curr_l.shape[0] // 2
        return orig(prev_l, curr_l.at[h:].set(prev_l[h:]), *args, **kw)
    pipeline._encode_shard = half
    return _run()


def exchange_left_out() -> dict:
    """The block that straddles two shards takes its tail from the fill
    instead of the next shard."""
    from repro.distributed import collectives
    collectives.right_edge_exchange = lambda x, axis, fill: fill
    return _run(**STRADDLE)


def centers_altered() -> dict:
    from repro.core import pipeline
    orig = pipeline.finalize_step
    calls = []

    def altered(*args, **kw):
        step = orig(*args, **kw)
        calls.append(1)
        if len(calls) == 6:                 # one field-step of the window
            step.centers = step.centers + 4e-3
        return step
    pipeline.finalize_step = altered
    return _run()


def control() -> dict:
    """The control as ``control.py`` places it (originals whole on one
    device) at both precisions, and at bfloat16 with the originals made on
    the mesh."""
    import tempfile
    import jax
    from chipbench import control as ctl
    config, traffic = _tiny()
    whole, chips = ctl.placement(CELL, config, traffic)
    with tempfile.TemporaryDirectory() as d:
        out = {p: ctl.readings(CELL, whole, traffic, 5, 8,
                               jax.devices()[:chips], d,
                               precision=p)["checks"]
               for p in ("bfloat16", "float32")}
        out["mesh"] = ctl.readings(CELL, config, traffic, 5, 8,
                                   jax.devices()[:4], d)["checks"]
    out["chips"] = chips
    return out


def generator() -> dict:
    """The sharded generator against itself, the unsharded one and the
    host generator it copies (``repro.data.temporal``'s 'asr')."""
    import jax
    import numpy as np
    from chipbench import data, harness, loadgen
    from chipbench.tests.test_data import _stats
    from repro.data.temporal import generate_series
    config = harness.load_config("cesm-atm-3d")
    shape = [8, 64, 64]
    sharding = loadgen.field_sharding(config, jax.devices())
    key = data.seed_key(2**31 + 11)

    def fields(gen):
        prev = gen.anchor(key, 0)
        change = gen.change(key, 0)
        return prev, change, gen.step(prev, change, key, 0, 1)
    a = fields(data.FieldGen(shape, "float32", config["stats"], sharding))
    b = fields(data.FieldGen(shape, "float32", config["stats"], sharding))
    c = fields(data.FieldGen(shape, "float32", config["stats"]))
    host = list(generate_series("asr", n_iterations=2, seed=0, scale=5))
    big = data.FieldGen(host[0].shape[:1] + (host[0].shape[1] // 4 * 4,)
                        + host[0].shape[2:], "float32", config["stats"],
                        sharding)
    prev, _, curr = fields(big)
    return {"shards": sorted([list(s.data.shape) for s in
                              a[2].addressable_shards]),
            "spec": str(a[2].sharding.spec),
            "same_run_to_run": all(np.array_equal(x, y)
                                   for x, y in zip(a, b)),
            "same_as_unsharded": all(np.array_equal(x, y)
                                     for x, y in zip(a, c)),
            "device": _stats(prev, curr), "host": _stats(*host)}


def files() -> dict:
    """One series compressed by the sharded driver and by
    ``TemporalCompressor``, each step written to an NCK file and decoded
    by the reference along its file chain."""
    import tempfile
    import jax
    import numpy as np
    from chipbench import data, harness, loadgen, reference as ref
    from repro.core.container import NCKWriter
    config = harness.load_config("cesm-atm-3d")
    config["shape"] = [8, 64, 64]
    devices = jax.devices()
    gen = data.FieldGen(config["shape"], "float32", config["stats"],
                        loadgen.field_sharding(config, devices))
    key = data.seed_key(9)
    pool = [gen.change(key, j) for j in range(2)]
    series = list(gen.series(key, 0, pool, [0, 1, 1, 0]))
    recons = {}
    with tempfile.TemporaryDirectory() as d:
        for driver in ("ShardedCompressor", "TemporalCompressor"):
            comp = loadgen.make_driver(dict(config, driver=driver), devices)
            got = np.asarray(series[0])
            comp.add(series[0])
            out = []
            for t, curr in enumerate(series[1:], start=1):
                path = os.path.join(d, f"{driver}{t}.nck")
                w = NCKWriter()
                w.add_step("s", comp.add(curr))
                w.write(path)
                got = ref.decode(ref.NCKFile(path), "s", got)[0]
                out.append(got)
            comp.close()
            recons[driver] = np.stack(out)
    a, b = recons["ShardedCompressor"], recons["TemporalCompressor"]
    return {"steps": len(a), "identical": bool(np.array_equal(a, b)),
            "differ": int(np.count_nonzero(a != b))}


CASES = {"generator": generator, "files": files, "correct": correct, "correct_straddle": lambda: correct(True),
         "state_unchanged": state_unchanged, "half_left_out": half_left_out,
         "exchange_left_out": exchange_left_out,
         "centers_altered": centers_altered, "control": control}


if __name__ == "__main__":
    import tempfile
    from chipbench.tests import conftest  # noqa: F401  (paths, CPU)
    from chipbench import run
    with tempfile.TemporaryDirectory() as state:
        run.STATE = state               # cases may run side by side
        print(json.dumps(CASES[sys.argv[1]]()), flush=True)
