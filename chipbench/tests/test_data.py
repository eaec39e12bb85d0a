"""The device generator has the statistics of the host generator it
copies (repro.data.temporal): the spread of the change ratios, the share
of jumps and the share of static cells."""
import numpy as np
import pytest

from chipbench import data
from repro.data.temporal import SPECS, generate_series


def _stats(prev, curr):
    prev = np.asarray(prev, np.float64).reshape(-1)
    curr = np.asarray(curr, np.float64).reshape(-1)
    r = (curr - prev) / prev
    moving = (np.abs(r) >= 1e-5) & (np.abs(r) <= 0.1)
    return {"jump": np.mean(np.abs(r) > 0.1),
            "static": np.mean(np.abs(r) < 1e-5),
            "spread": np.std(r[moving])}


def _spec_stats(name):
    s = SPECS[name]
    return {"vol": s.vol, "jump_frac": s.jump_frac,
            "static_frac": s.static_frac, "offset": s.offset,
            "slope": s.slope}


@pytest.mark.parametrize("spec", ["isabel", "cmip"])
def test_device_step_matches_host_statistics(spec):
    host = list(generate_series(spec, n_iterations=2, seed=0, scale=5))
    shape = host[0].shape
    gen = data.FieldGen(shape, "float32", _spec_stats(spec))
    key = data.seed_key(2**31 + 11)
    prev = gen.anchor(key, 0)
    curr = gen.step(prev, gen.change(key, 0), key, 0, 1)
    assert curr.shape == shape and curr.dtype == np.float32
    want, got = _stats(*host), _stats(prev, curr)
    assert got["jump"] == pytest.approx(want["jump"], rel=0.3)
    assert got["static"] == pytest.approx(want["static"], rel=0.1)
    assert got["spread"] == pytest.approx(want["spread"], rel=0.1)


def test_same_seed_same_data_other_seed_other_data():
    gen = data.FieldGen((8, 32, 32), "float32", _spec_stats("isabel"))
    a = np.asarray(gen.anchor(data.seed_key(3_000_000_001), 2))
    b = np.asarray(gen.anchor(data.seed_key(3_000_000_001), 2))
    c = np.asarray(gen.anchor(data.seed_key(3_000_000_002), 2))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)



def test_every_block_of_draws_holds_each_change_field_once(tmp_path):
    import jax
    from chipbench import harness, loadgen
    from chipbench.tests._cells import tiny
    _, _, config, traffic = tiny("isabel.write")
    orders = []
    for seed in (5, 6):
        load = loadgen.make_load(config, traffic, seed, jax.devices()[:1],
                                 str(tmp_path), harness.Spans())
        p = len(load.pool)
        draws = [load.draw_change(0, t) for t in range(1, 3 * p + 1)]
        for b in range(3):
            assert sorted(draws[b * p:(b + 1) * p]) == list(range(p))
        orders.append(draws)
    assert orders[0] != orders[1]
