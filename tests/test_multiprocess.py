"""Multi-process tier: launch emulation, per-rank shard writers, NCKM
manifest commit/recovery, and 2-process byte-identity.

The fast tests exercise the container/launch layers in-process (hand-made
anchor fragments -- blocks compress independently, so a readable logical
file needs no compressor).  The slow tests spawn real
``jax.distributed``-initialized subprocess fleets through
``repro.launch.distributed.spawn_emulated`` -- the identical launch path
``make bench-all``'s scaling bench and a real multi-host run use.
"""
import json
import os
import struct
import subprocess
import sys
import textwrap
import zlib

import numpy as np
import pytest

from repro.core import container
from repro.core.container import (NCKReader, ShardNCKWriter, StepFragment,
                                  atomic_commit, rank_file_path,
                                  read_manifest, write_manifest)
from repro.launch import runtime_env as renv
from repro.launch.distributed import (ENV_COORDINATOR, ENV_NUM_PROCESSES,
                                      ENV_PROCESS_ID, rank_env,
                                      spawn_emulated)

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                    "src"))


# ------------------------------------------------------------ atomic commit

def test_atomic_commit_bytes_and_chunks(tmp_path):
    p = str(tmp_path / "out.bin")
    atomic_commit(p, b"hello")
    assert open(p, "rb").read() == b"hello"
    # chunked overwrite of an existing file, no tmp debris left behind
    atomic_commit(p, iter([b"a", b"bc", b""]))
    assert open(p, "rb").read() == b"abc"
    assert os.listdir(tmp_path) == ["out.bin"]


def test_atomic_commit_failure_leaves_target(tmp_path):
    p = str(tmp_path / "out.bin")
    atomic_commit(p, b"v1")

    def boom():
        yield b"partial"
        raise IOError("disk gone")

    with pytest.raises(IOError):
        atomic_commit(p, boom())
    assert open(p, "rb").read() == b"v1"


# ------------------------------------------------------- launch environment

def test_runtime_env_preset():
    base = {"XLA_FLAGS": "--xla_cpu_enable_fast_math=false"}
    env = renv.runtime_env(base, host_device_count=4)
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "--xla_cpu_enable_fast_math=false" in env["XLA_FLAGS"]
    assert base == {"XLA_FLAGS": "--xla_cpu_enable_fast_math=false"}
    if renv.find_tcmalloc():
        assert "tcmalloc" in env["LD_PRELOAD"]
        assert env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] \
            == renv.TCMALLOC_REPORT_THRESHOLD


def test_merge_xla_flags_dedups_by_key():
    merged = renv.merge_xla_flags(
        "--xla_force_host_platform_device_count=2 --a=1",
        ["--xla_force_host_platform_device_count=8"])
    assert merged.split().count("--a=1") == 1
    assert "--xla_force_host_platform_device_count=8" in merged
    assert "--xla_force_host_platform_device_count=2" not in merged


_CACHE_CHILD = textwrap.dedent("""
    import sys
    import jax
    import jax.numpy as jnp
    from repro.launch.runtime_env import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(enable_compile_cache(sys.argv[1]))
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(257)).block_until_ready()
""")


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_directory(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set;
    otherwise the cache sits at the fixed <root>/.jax_cache."""
    root = tmp_path / "checkout"
    env = {k: v for k, v in os.environ.items()
           if k != renv.COMPILE_CACHE_ENV}
    env.update(PYTHONPATH=_SRC, JAX_PLATFORMS="cpu")
    want = root / renv.COMPILE_CACHE_DIRNAME
    if from_env:
        want = tmp_path / "placed"
        env[renv.COMPILE_CACHE_ENV] = str(want)
    out = subprocess.run([sys.executable, "-c", _CACHE_CHILD, str(root)],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir()), "no compiled executable was cached"
    assert root.exists() != from_env


def test_rank_env_coordinates():
    env = rank_env(1, 4, "localhost:1234", devices_per_process=2,
                   base={}, preset=True)
    assert env[ENV_COORDINATOR] == "localhost:1234"
    assert env[ENV_NUM_PROCESSES] == "4"
    assert env[ENV_PROCESS_ID] == "1"
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]


@pytest.mark.parametrize("preset", [True, False])
def test_rank_env_pins_emulated_ranks_to_cpu(preset):
    """Emulated ranks never try to take an accelerator their parent holds."""
    env = rank_env(0, 2, "localhost:1234", base={"JAX_PLATFORMS": "tpu"},
                   preset=preset)
    assert env["JAX_PLATFORMS"] == "cpu"


def test_spawn_emulated_ranks_and_failure_reporting():
    code = ("import os,sys;"
            "print('rank', os.environ['REPRO_PROCESS_ID']);"
            "sys.exit(int(os.environ['REPRO_PROCESS_ID']))")
    res = spawn_emulated(2, ["-c", code], timeout=60)
    assert [r.returncode for r in res] == [0, 1]
    assert "rank 0" in res[0].stdout and "rank 1" in res[1].stdout


# ------------------------------------------------- manifest + shard writers

def _anchor_fragments(arr: np.ndarray, num_ranks: int):
    """Hand-made lossless anchor split across `num_ranks`, mirroring
    MultiProcessCompressor._anchor_fragment's block ownership."""
    from repro.core import pipeline as pipe
    flat = arr.reshape(-1)
    be = 8
    slices = pipe.block_slices(flat.size, be)
    nb = len(slices)
    info = dict(total_data_num=arr.size, shape=list(arr.shape),
                dtype=str(arr.dtype), bin_centers_number=0,
                elements_per_block=be, B=0, error_bound=1e-3,
                strategy="topk", reference="reconstructed", domain_lo=0.0,
                bin_width=0.0, is_anchor=True, n_blocks=nb, codec="zlib")
    frags = []
    for rank in range(num_ranks):
        lo = rank * nb // num_ranks
        hi = (rank + 1) * nb // num_ranks
        blks = [zlib.compress(flat[s:e].tobytes(), 6)
                for s, e in slices[lo:hi]]
        frags.append(StepFragment(is_anchor=True, block_start=lo,
                                  info=dict(info), index_blocks=blks))
    return frags


def _write_logical(path: str, arr: np.ndarray, num_ranks: int,
                   generation=None) -> str:
    frags = _anchor_fragments(arr, num_ranks)
    manifest = None
    for rank in range(num_ranks):
        w = ShardNCKWriter(path, rank, num_ranks, generation=generation)
        w.add_fragment("step0000", frags[rank])
        w.write()
        if rank == 0:
            rank0 = w
    manifest = rank0.commit_manifest(timeout=5.0)
    return manifest


def test_manifest_roundtrip_two_ranks(tmp_path):
    path = str(tmp_path / "series.nck")
    arr = np.arange(100, dtype=np.float32)
    _write_logical(path, arr, 2)
    assert sorted(os.listdir(tmp_path)) == [
        "series.nck", "series.nck.g0000.rank0", "series.nck.g0000.rank1"]
    r = NCKReader(path)
    assert r.step_names() == ["step0000"]
    step = r.read_step("step0000")
    assert step.is_anchor
    from repro.core.compress import decode_anchor
    np.testing.assert_array_equal(decode_anchor(step), arr)


def test_reader_rejects_missing_shard(tmp_path):
    path = str(tmp_path / "series.nck")
    _write_logical(path, np.arange(64, dtype=np.float32), 2)
    missing = rank_file_path(path, 0, 1)
    os.remove(missing)
    with pytest.raises(FileNotFoundError) as ei:
        NCKReader(path)
    assert os.path.basename(missing) in str(ei.value)
    assert "rank 1" in str(ei.value)


def test_reader_rejects_truncated_shard(tmp_path):
    path = str(tmp_path / "series.nck")
    _write_logical(path, np.arange(64, dtype=np.float32), 2)
    victim = rank_file_path(path, 0, 1)
    data = open(victim, "rb").read()
    with open(victim, "wb") as f:
        f.write(data[:-3])
    with pytest.raises(ValueError, match="bytes"):
        NCKReader(path)


def test_generation_bump_and_gc(tmp_path):
    path = str(tmp_path / "series.nck")
    arr = np.arange(80, dtype=np.float32)
    _write_logical(path, arr, 2)
    assert read_manifest(path)["generation"] == 0
    _write_logical(path, arr * 2, 2)          # next_generation() picks 1
    m = read_manifest(path)
    assert m["generation"] == 1
    # generation 0 is retained as the rollback target (manifest embeds it
    # under "previous"); its shard files survive GC
    assert m["previous"]["generation"] == 0
    assert sorted(os.listdir(tmp_path)) == [
        "series.nck",
        "series.nck.g0000.rank0", "series.nck.g0000.rank1",
        "series.nck.g0001.rank0", "series.nck.g0001.rank1"]
    _write_logical(path, arr * 3, 2)          # generation 2
    # now generation 0 is unreachable (previous == 1) and is GC'd
    assert sorted(os.listdir(tmp_path)) == [
        "series.nck",
        "series.nck.g0001.rank0", "series.nck.g0001.rank1",
        "series.nck.g0002.rank0", "series.nck.g0002.rank1"]
    step = NCKReader(path).read_step("step0000")
    from repro.core.compress import decode_anchor
    np.testing.assert_array_equal(decode_anchor(step), arr * 3)


def test_commit_timeout_preserves_previous_manifest(tmp_path):
    path = str(tmp_path / "series.nck")
    arr = np.arange(48, dtype=np.float32)
    _write_logical(path, arr, 2)              # generation 0, loadable
    # generation 1: rank 0 writes, rank 1 "crashed" (file never appears)
    frag = _anchor_fragments(arr, 2)[0]
    w = ShardNCKWriter(path, 0, 2)
    w.add_fragment("step0000", frag)
    w.write()
    with pytest.raises(TimeoutError, match="previous manifest"):
        w.commit_manifest(timeout=0.3)
    # the logical file still opens at generation 0
    r = NCKReader(path)
    assert read_manifest(path)["generation"] == 0
    from repro.core.compress import decode_anchor
    np.testing.assert_array_equal(
        decode_anchor(r.read_step("step0000")), arr)


def test_manifest_magic_rejects_corruption(tmp_path):
    path = str(tmp_path / "series.nck")
    _write_logical(path, np.arange(32, dtype=np.float32), 1)
    raw = open(path, "rb").read()
    assert raw[:4] == container._MANIFEST_MAGIC
    hlen = struct.unpack("<Q", raw[4:12])[0]
    assert json.loads(raw[12:12 + hlen])["schema"] == 2
    with open(path, "wb") as f:
        f.write(b"XXXX" + raw[4:])
    with pytest.raises(Exception):
        NCKReader(path)


# ---------------------------------------------------- multi-process (slow)

def _make_series_src(n=50_777, steps=4):
    return textwrap.dedent(f"""
        import numpy as np
        rng = np.random.default_rng(7)
        n = {n}
        base = rng.normal(1.0, 0.5, n).astype(np.float32)
        series = [base]
        for t in range({steps} - 1):
            nxt = (series[-1] * (1 + 0.01 * rng.standard_normal(n))
                   ).astype(np.float32)
            nxt[t::401] *= 40.0
            series.append(nxt)
    """)


_MP_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    from repro.launch import distributed as dist
    cfg = dist.initialize()
    mesh = dist.global_mesh()
    import jax
    assert jax.process_count() == 2, jax.process_count()

    # Structural no-payload-gather proof: fetching a P(axis)-sharded
    # array whole from one process raises; only addressable shards (the
    # per-rank writer's entire input) are host-fetchable.
    from repro.distributed.pipeline import (MultiProcessCompressor,
                                            _put_sharded)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharded = NamedSharding(mesh, P("data"))
    probe = _put_sharded(np.arange(8, dtype=np.float32), sharded)
    try:
        np.asarray(probe)
        raise SystemExit("cross-process fetch unexpectedly succeeded")
    except RuntimeError:
        pass

    from repro.core import NumarckParams
    {series_src}
    mp = MultiProcessCompressor(mesh, params=NumarckParams(
        error_bound=1e-3), use_pallas=False)
    if os.environ.get("CRASH_RANK", "") == str(cfg.process_id):
        mp.compress_series_fragments(series)   # collectives complete...
        mp.close()
        os._exit(3)                            # ...then die pre-publish
    out = mp.save_series(os.environ["OUT_PATH"], series,
                         manifest_timeout=float(
                             os.environ.get("MANIFEST_TIMEOUT", "60")))
    mp.close()
    print("WORKER_OK", out)
""")


def _spawn_workers(out_path, *, crash_rank=None, manifest_timeout=None,
                   timeout=240):
    env = dict(os.environ)
    env["OUT_PATH"] = out_path
    env["PYTHONPATH"] = _SRC
    if crash_rank is not None:
        env["CRASH_RANK"] = str(crash_rank)
    if manifest_timeout is not None:
        env["MANIFEST_TIMEOUT"] = str(manifest_timeout)
    script = _MP_WORKER.format(series_src=_make_series_src())
    return spawn_emulated(2, ["-c", script], base_env=env, timeout=timeout)


# Single-process reference over the SAME 2-device mesh (the block grid
# follows the shard layout, so an equal-device ShardedCompressor run is
# the byte-identity baseline; ShardedCompressor == single-device is
# covered by tests/test_distributed.py).
_SINGLE_REF = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core import NumarckParams
    from repro.core.container import NCKWriter
    from repro.distributed.pipeline import ShardedCompressor
    {series_src}
    sc = ShardedCompressor(Mesh(np.array(jax.devices()), ("data",)),
                           params=NumarckParams(error_bound=1e-3),
                           use_pallas=False)
    steps = sc.compress_series(series)
    sc.close()
    w = NCKWriter()
    for i, s in enumerate(steps):
        w.add_step(f"step{{i:04d}}", s)
    w.write(os.environ["REF_PATH"])
    print("REF_OK")
""")


@pytest.mark.slow
def test_two_process_byte_identity(tmp_path):
    """2-process save_series == single-process compress_series, byte for
    byte (blocks, centers, exceptions), with per-rank shard files plus a
    rank-0 manifest and zero cross-process payload fetches."""
    path = str(tmp_path / "series.nck")
    res = _spawn_workers(path)
    for rank, r in enumerate(res):
        assert r.returncode == 0, (
            f"rank {rank}:\n{r.stdout}\n{r.stderr}")
        assert "WORKER_OK" in r.stdout
    assert sorted(os.listdir(tmp_path)) == [
        "series.nck", "series.nck.g0000.rank0", "series.nck.g0000.rank1"]

    ref_path = str(tmp_path / "ref.nck")
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env["REF_PATH"] = ref_path
    script = _SINGLE_REF.format(series_src=_make_series_src())
    ref = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert ref.returncode == 0, ref.stdout + ref.stderr

    got, want = NCKReader(path), NCKReader(ref_path)
    names = got.step_names()
    assert names == [f"step{i:04d}" for i in range(4)]
    for name in names:
        a, b = got.read_step(name), want.read_step(name)
        assert a.is_anchor == b.is_anchor
        assert len(a.index_blocks) == len(b.index_blocks)
        for j, (x, y) in enumerate(zip(a.index_blocks, b.index_blocks)):
            assert x == y, f"{name} block {j} differs"
        if not a.is_anchor:
            assert a.b_bits == b.b_bits and a.n == b.n
            np.testing.assert_array_equal(np.asarray(a.centers),
                                          np.asarray(b.centers))
            np.testing.assert_array_equal(a.incomp_values,
                                          b.incomp_values)
            np.testing.assert_array_equal(a.incomp_block_offsets,
                                          b.incomp_block_offsets)


@pytest.mark.slow
def test_crashed_rank_leaves_previous_manifest(tmp_path):
    """A rank dying after the collectives but before publishing its
    shard file must not corrupt the logical file: rank 0's manifest
    commit times out and the previous generation stays loadable."""
    path = str(tmp_path / "series.nck")
    res = _spawn_workers(path)                 # generation 0, both ranks
    assert [r.returncode for r in res] == [0, 0], [
        (r.returncode, r.stderr[-800:]) for r in res]
    before = NCKReader(path)
    baseline = {n: before.read_step(n).index_blocks
                for n in before.step_names()}

    res = _spawn_workers(path, crash_rank=1, manifest_timeout=3)
    assert res[1].returncode == 3              # the planted crash
    assert res[0].returncode != 0              # TimeoutError surfaced
    assert "TimeoutError" in res[0].stderr

    after = NCKReader(path)
    assert read_manifest(path)["generation"] == 0
    for n, blocks in baseline.items():
        assert after.read_step(n).index_blocks == blocks
