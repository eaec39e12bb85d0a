"""Cells cut to a size the CPU runs in seconds."""
import json
import time

import jax

from chipbench import harness, run

SHAPE = [8, 64, 64]


def tiny(cell_name: str, fields: int = 3, **params):
    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, cell_name)
    config = harness.load_config(cell["config"])
    config["shape"] = SHAPE
    config["fields"] = config["fields"][:fields]
    config["params"] = dict(config["params"], **params)
    return bench, cell, config, harness.load_traffic(cell["traffic"])


def run_tiny(cell_name: str, seed: int = 7, seconds: float = 1.0,
             **params) -> dict:
    bench, cell, config, traffic = tiny(cell_name, **params)
    line = run.run_cell(bench, cell, seed, seconds, False,
                        jax.devices()[:cell["chips"]],
                        start=time.monotonic(), config=config,
                        traffic=traffic)
    return json.loads(line)
