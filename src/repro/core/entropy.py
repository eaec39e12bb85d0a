"""Pluggable host-side entropy stage with a parallel block dispatcher.

The paper runs ZLIB on the CPU cores as the final compression phase
(Sec. IV-C); arXiv:1903.07761 generalizes that into a stage-structured
pipeline whose entropy back-end is *pluggable* and thread-parallel.  This
module is our version of that idea:

  * a codec registry -- ``zlib`` (default), ``raw`` (store), ``lzma`` and
    ``bz2`` behind one two-method interface; new codecs register with
    :func:`register_codec` and are persisted by name in the NCK container
    so files remain self-describing.
  * :func:`compress_blocks` -- the one entropy entry point used by every
    compressor (single-device, sharded, anchors).  Blocks are batched and
    dispatched over a shared ``ThreadPoolExecutor``; zlib/bz2/lzma all
    release the GIL on the C side, so threads give real parallel speedup
    (see ``benchmarks/bench_entropy.py``).  Codecs that *hold* the GIL
    (``Codec.holds_gil = True``) are dispatched over a spawned
    ``ProcessPoolExecutor`` instead, with a transparent serial fallback
    when process pools are unavailable.
  * the ``"auto"`` pseudo-codec id -- :func:`resolve_codec` probes a
    sampled prefix of the payload with a fast zlib pass and picks
    raw / zlib / lzma from the measured compressibility (the per-chunk
    adaptive codec choice of LCP, arXiv:2411.00761).  ``"auto"`` is a
    *parameter-level* id only: finalize resolves it per step and the NCK
    container always persists a concrete registry name.

Batching heuristic (benchmarked in bench_entropy.py): tasks are groups of
consecutive blocks sized so that (a) every worker gets work and (b) each
task carries at least ``_TARGET_TASK_BYTES`` of payload so submission
overhead stays <1% even for tiny blocks.
"""
from __future__ import annotations

import bz2
import lzma
import multiprocessing
import os
import threading
import time
import zlib
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

from repro.faults import inject
from repro.faults.errors import IntegrityError
from repro.obs import telemetry

# --------------------------------------------------------------------- codecs


class Codec:
    """Entropy codec interface: bytes -> bytes, self-inverse via decompress."""

    name: str = "abstract"
    # Pure-python codecs that never release the GIL get no speedup from the
    # thread pool; mark them and compress_blocks dispatches them over a
    # spawned process pool instead.
    holds_gil: bool = False
    # Codecs with a device-resident encoder: the drivers can entropy-code
    # index blocks on the accelerator (kernels.rans) and hand finalize
    # pre-compressed blobs byte-identical to this host flavor.
    device: bool = False

    def compress(self, raw: bytes, level: int) -> bytes:
        raise NotImplementedError

    def decompress(self, blob: bytes) -> bytes:
        raise NotImplementedError


class ZlibCodec(Codec):
    name = "zlib"

    def compress(self, raw: bytes, level: int) -> bytes:
        return zlib.compress(raw, level)

    def decompress(self, blob: bytes) -> bytes:
        return zlib.decompress(blob)


class RawCodec(Codec):
    """Store-only codec: no entropy coding (fastest finalize, CR from
    binning alone).  Useful when the index table is near-incompressible or
    the host is the bottleneck."""

    name = "raw"

    def compress(self, raw: bytes, level: int) -> bytes:
        return raw

    def decompress(self, blob: bytes) -> bytes:
        return blob


class LzmaCodec(Codec):
    """LZMA: slowest, highest ratio; level maps to preset 0-9."""

    name = "lzma"

    def compress(self, raw: bytes, level: int) -> bytes:
        return lzma.compress(raw, preset=min(max(level, 0), 9))

    def decompress(self, blob: bytes) -> bytes:
        return lzma.decompress(blob)


class Bz2Codec(Codec):
    name = "bz2"

    def compress(self, raw: bytes, level: int) -> bytes:
        return bz2.compress(raw, compresslevel=min(max(level, 1), 9))

    def decompress(self, blob: bytes) -> bytes:
        return bz2.decompress(blob)


class RansCodec(Codec):
    """Block-parallel interleaved rANS (kernels.rans).

    This registry entry is the *host* (NumPy) flavor -- a lane-vectorized
    python loop, hence ``holds_gil``.  ``device=True`` advertises the
    accelerator encoder: drivers route index blocks through
    ``kernels.rans.compress_blocks_device`` (or the sharded shard_map
    stage) and finalize consumes the pre-compressed blobs; both flavors
    emit byte-identical self-describing blobs, so files do not record
    which one produced them.  The kernels module is imported lazily to
    keep this module import-light (process-pool workers, NumarckParams
    validation).
    """

    name = "rans"
    # Deliberately NOT holds_gil: process-pool dispatch would ship every
    # block by pickle to spawned workers.  The host flavor therefore
    # serializes under the GIL -- it is the correctness/fallback path;
    # throughput comes from the device stage.
    device = True

    def compress(self, raw: bytes, level: int) -> bytes:
        from repro.kernels import rans
        return rans.compress(raw)

    def decompress(self, blob: bytes) -> bytes:
        from repro.kernels import rans
        return rans.decompress(blob)


DEFAULT_CODEC = "zlib"
AUTO_CODEC = "auto"
_REGISTRY: Dict[str, Codec] = {}


def register_codec(codec: Codec) -> Codec:
    _REGISTRY[codec.name] = codec
    return codec


def get_codec(name: str) -> Codec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown codec {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def codec_names() -> List[str]:
    return sorted(_REGISTRY)


def validate_codec_id(name: str) -> str:
    """Accept any registered codec plus the ``"auto"`` pseudo-id.

    Parameters may carry ``"auto"``; persisted steps never do (finalize
    resolves it to a concrete registry name first).
    """
    if name != AUTO_CODEC:
        get_codec(name)                  # raises on unknown codec
    return name


for _c in (ZlibCodec(), RawCodec(), LzmaCodec(), Bz2Codec(), RansCodec()):
    register_codec(_c)

# ------------------------------------------------------ adaptive selection

# Probe: deflate a bounded prefix at level 1 (cheap, ~100 MB/s) and read the
# achieved ratio.  Thresholds picked from benchmarks/bench_entropy.py on
# zipf index tables vs random bytes: near-incompressible payloads waste
# zlib time for <3% size, while highly redundant payloads close most of
# the lzma-vs-zlib gap at acceptable cost.
_AUTO_SAMPLE_BYTES = 64 << 10
_AUTO_RAW_THRESHOLD = 0.95       # probe ratio above this -> store raw
_AUTO_LZMA_THRESHOLD = 0.30      # probe ratio below this -> lzma pays off
# lzma is 10-40x slower than zlib; cap the payload size we are willing to
# hand it so finalize latency stays bounded on huge steps.
_AUTO_LZMA_MAX_BYTES = 256 << 20


def _probe_one(raw: bytes, allow_lzma: bool = True) -> str:
    """One compressibility probe -> concrete codec (the auto policy)."""
    if not raw:
        return DEFAULT_CODEC
    sample = raw[:_AUTO_SAMPLE_BYTES]
    ratio = len(zlib.compress(sample, 1)) / len(sample)
    if ratio >= _AUTO_RAW_THRESHOLD:
        return "raw"
    if ratio <= _AUTO_LZMA_THRESHOLD and allow_lzma:
        return "lzma"
    return DEFAULT_CODEC


def choose_codec(raws: Sequence[bytes], level: int = 6) -> str:
    """Pick a concrete codec from the measured compressibility of a sampled
    block prefix (LCP-style per-chunk adaptivity, arXiv:2411.00761)."""
    del level
    total = sum(len(r) for r in raws)
    for r in raws:
        if r:
            return _probe_one(r, allow_lzma=total <= _AUTO_LZMA_MAX_BYTES)
    return DEFAULT_CODEC


def resolve_codec(codec: str, raws: Sequence[bytes], level: int = 6) -> str:
    """Map the parameter-level codec id to the concrete one used for this
    payload.  Identity for everything but ``"auto"``."""
    if codec == AUTO_CODEC:
        return choose_codec(raws, level)
    get_codec(codec)
    return codec


def choose_block_codecs(raws: Sequence[bytes], level: int = 6) -> List[str]:
    """Per-*block* codec choice: the ``"auto"`` probe applied to every
    block rather than only the first one, so mixed hot/cold ranges get
    mixed codecs (near-incompressible blocks go raw, highly redundant
    blocks go lzma) and the NCK container persists one id per block.

    The lzma latency cap stays a *total*-payload bound, exactly as in
    :func:`choose_codec` -- a huge step must not go 10-40x slower just
    because each individual block is small.  Probes are dispatched over
    the shared thread pool on large payloads (zlib releases the GIL), so
    the per-block policy adds no serial stall to the finalize path.
    """
    del level
    total = sum(len(r) for r in raws)
    allow_lzma = total <= _AUTO_LZMA_MAX_BYTES
    if len(raws) >= 4 and total >= _MIN_PARALLEL_BYTES:
        picks = list(_shared_pool().map(
            lambda r: _probe_one(r, allow_lzma), raws))
    else:
        picks = [_probe_one(r, allow_lzma) for r in raws]
    if telemetry.enabled():
        for p in set(picks):
            telemetry.counter(f"entropy.auto.pick.{p}",
                              float(picks.count(p)))
    return picks

# ----------------------------------------------------------- parallel stage

# Below this total payload the pool overhead exceeds the win; stay serial.
_MIN_PARALLEL_BYTES = 1 << 20
# Batch consecutive blocks until each task carries at least this much.
_TARGET_TASK_BYTES = 2 << 20
# Per-task ceiling for process-pool results; beyond it the pool is marked
# broken and the codec degrades to the (serializing but correct) threads.
_PROC_RESULT_TIMEOUT_S = 120.0

_pool_lock = threading.Lock()
_pool: Optional[ThreadPoolExecutor] = None
_proc_pool: Optional[ProcessPoolExecutor] = None
_proc_pool_broken = False


def _shared_pool() -> ThreadPoolExecutor:
    """Process-wide entropy pool (lazily created; sized to the host CPUs)."""
    global _pool
    with _pool_lock:
        if _pool is None:
            workers = min(32, os.cpu_count() or 1)
            _pool = ThreadPoolExecutor(max_workers=workers,
                                       thread_name_prefix="entropy")
        return _pool


def _register_codecs(codecs: List[Codec]) -> None:
    """Process-pool worker initializer: the parent's codec registry."""
    for c in codecs:
        register_codec(c)


def _shared_proc_pool() -> Optional[ProcessPoolExecutor]:
    """Spawned process pool for GIL-holding codecs.

    Spawn, not fork: the parent may hold an accelerator (and JAX's
    runtime threads), and a forked child would inherit that state.
    Workers register the parent's codecs as they stand when the pool is
    first used; codecs registered after that are not visible to workers
    -- register before compressing.  Returns None where process pools
    are unavailable (callers fall back to the thread pool, which is
    correct, just not parallel).
    """
    global _proc_pool, _proc_pool_broken
    with _pool_lock:
        if _proc_pool is None and not _proc_pool_broken:
            try:
                ctx = multiprocessing.get_context("spawn")
                workers = min(8, os.cpu_count() or 1)
                _proc_pool = ProcessPoolExecutor(
                    max_workers=workers, mp_context=ctx,
                    initializer=_register_codecs,
                    initargs=(list(_REGISTRY.values()),))
            except (ValueError, OSError):
                _proc_pool_broken = True
        return _proc_pool


def _retire_proc_pool(px: ProcessPoolExecutor):
    """Permanently disable process dispatch and tear the pool down (without
    waiting on possibly-wedged workers)."""
    global _proc_pool, _proc_pool_broken
    with _pool_lock:
        _proc_pool_broken = True
        if _proc_pool is px:
            _proc_pool = None
    px.shutdown(wait=False, cancel_futures=True)


def _compress_batch(codec_name: str, raws: List[bytes],
                    level: int) -> List[bytes]:
    """Process-pool task body: resolve the codec by name in the worker."""
    # Injection site: a dying pool worker must exercise the
    # retire-and-degrade path in _dispatch_blocks, not hang the driver.
    inject.fire("entropy_worker_death", codec=codec_name, blocks=len(raws))
    c = get_codec(codec_name)
    return [c.compress(r, level) for r in raws]


def _task_plan(sizes: Sequence[int], workers: int) -> List[range]:
    """Group consecutive block indices into tasks.

    At least `workers` tasks (so every core is busy) unless the payload is
    small; no task smaller than one block; tasks cover blocks in order so
    output order is positional.
    """
    total = sum(sizes)
    n = len(sizes)
    n_tasks = max(workers, total // _TARGET_TASK_BYTES)
    n_tasks = max(1, min(n, n_tasks))
    step = -(-n // n_tasks)
    return [range(s, min(s + step, n)) for s in range(0, n, step)]


def compress_blocks(raws: Sequence[bytes], codec: str = DEFAULT_CODEC,
                    level: int = 6, parallel: bool = True,
                    pool: Optional[ThreadPoolExecutor] = None) -> List[bytes]:
    """Entropy-code every block; the single finalize entry point.

    Serial for small payloads, thread-parallel (shared pool, batched tasks)
    otherwise.  Output is byte-identical to the serial loop in both modes --
    per-block codec streams are independent.
    """
    codec = resolve_codec(codec, raws, level)
    c = get_codec(codec)
    sizes = [len(r) for r in raws]
    with telemetry.span("entropy.compress", codec=codec,
                        blocks=len(raws)) as sp:
        out = _dispatch_blocks(c, codec, raws, sizes, level, parallel, pool)
        if telemetry.enabled():
            bytes_in, bytes_out = sum(sizes), sum(len(b) for b in out)
            telemetry.counter(f"entropy.bytes_in.{codec}", float(bytes_in))
            telemetry.counter(f"entropy.bytes_out.{codec}", float(bytes_out))
            sp.set(bytes_in=bytes_in, bytes_out=bytes_out)
    return out


def _dispatch_blocks(c: Codec, codec: str, raws: Sequence[bytes],
                     sizes: List[int], level: int, parallel: bool,
                     pool: Optional[ThreadPoolExecutor]) -> List[bytes]:
    """Serial / thread-pool / process-pool dispatch of compress_blocks."""
    if (not parallel or len(raws) < 2
            or sum(sizes) < _MIN_PARALLEL_BYTES):
        return [c.compress(r, level) for r in raws]

    if c.holds_gil and pool is None:
        # GIL-holding codec: threads would serialize, so fan batches out to
        # spawned worker processes instead (payload ships by pickle; the
        # >= _TARGET_TASK_BYTES batching keeps the IPC amortized).  The
        # result timeout is the backstop: a wedged child degrades us to
        # the thread path instead of hanging the finalize stage.
        px = _shared_proc_pool()
        if px is not None:
            workers = getattr(px, "_max_workers", os.cpu_count() or 1)
            plan = _task_plan(sizes, workers)
            try:
                futs = [px.submit(_compress_batch, codec,
                                  [raws[i] for i in rng], level)
                        for rng in plan]
                out = []
                for f in futs:
                    out.extend(f.result(timeout=_PROC_RESULT_TIMEOUT_S))
                return out
            except Exception:
                # Sandboxed spawn, wedged worker, codec error in the child:
                # retire the pool entirely (a wedged pool would otherwise
                # re-stall every later call) and degrade to threads.  If
                # the codec itself is at fault the thread path below
                # re-raises the same error to the caller.
                _retire_proc_pool(px)

    ex = pool or _shared_pool()
    workers = getattr(ex, "_max_workers", os.cpu_count() or 1)
    # Submit->start latency of each pool task: a loaded pool shows up as a
    # fat entropy.queue_wait_s histogram, not as mystery finalize time.
    tele = telemetry.enabled()
    t_submit = time.perf_counter() if tele else 0.0

    def run(rng: range) -> List[bytes]:
        if not tele:
            return [c.compress(raws[i], level) for i in rng]
        telemetry.histo("entropy.queue_wait_s",
                        time.perf_counter() - t_submit)
        with telemetry.span("entropy.batch", codec=codec, blocks=len(rng)):
            return [c.compress(raws[i], level) for i in rng]

    out: List[bytes] = []
    for part in ex.map(run, _task_plan(sizes, workers)):
        out.extend(part)
    return out


def compress_blocks_per_codec(raws: Sequence[bytes], codecs: Sequence[str],
                              level: int = 6,
                              parallel: bool = True) -> List[bytes]:
    """Entropy-code every block with its *own* codec id.

    One pool dispatch over all blocks (codecs interleaved, parallel
    threshold on the *step* total, not per-codec-group totals), so a
    small lzma group never serializes behind a big zlib group.  Per-block
    output is byte-identical to compressing every block alone -- block
    streams are independent whatever the dispatch.  GIL-holding codecs
    stay correct here but serialize; the mixed-codec path is only used
    by the ``"auto"`` palette (raw/zlib/lzma), which releases the GIL.
    """
    assert len(raws) == len(codecs)
    pairs = [(r, get_codec(c)) for r, c in zip(raws, codecs)]
    with telemetry.span("entropy.compress_per_codec", blocks=len(raws)):
        if (not parallel or len(raws) < 2
                or sum(len(r) for r in raws) < _MIN_PARALLEL_BYTES):
            out = [c.compress(r, level) for r, c in pairs]
        else:
            ex = _shared_pool()
            out = list(ex.map(lambda rc: rc[1].compress(rc[0], level),
                              pairs))
    if telemetry.enabled():
        for cname in set(codecs):
            bi = sum(len(r) for r, c in zip(raws, codecs) if c == cname)
            bo = sum(len(b) for b, c in zip(out, codecs) if c == cname)
            telemetry.counter(f"entropy.bytes_in.{cname}", float(bi))
            telemetry.counter(f"entropy.bytes_out.{cname}", float(bo))
    return out


def _decompress_one(c: Codec, codec: str, blob: bytes) -> bytes:
    """Decode one blob, converting codec-internal failures (zlib.error,
    lzma format errors, rANS final-state mismatches ...) into a
    structured :class:`IntegrityError` -- a corrupt block must fail
    loudly at the entropy stage, never as a traceback from deep inside a
    codec (and never as silently wrong bytes)."""
    try:
        return c.decompress(blob)
    except IntegrityError:
        raise
    except Exception as e:
        raise IntegrityError(
            f"entropy decode failed: codec {codec!r} rejected a "
            f"{len(blob)}-byte blob ({e!r}) -- block is corrupt or "
            "truncated") from e


def decompress_block(blob: bytes, codec: str = DEFAULT_CODEC) -> bytes:
    return _decompress_one(get_codec(codec), codec, blob)


def decompress_blocks(blobs: Sequence[bytes], codec: str = DEFAULT_CODEC,
                      parallel: bool = True) -> List[bytes]:
    """Inverse of compress_blocks (parallel when the payload warrants it)."""
    c = get_codec(codec)
    if not parallel or len(blobs) < 2 \
            or sum(len(b) for b in blobs) < _MIN_PARALLEL_BYTES:
        return [_decompress_one(c, codec, b) for b in blobs]
    ex = _shared_pool()
    return list(ex.map(lambda b: _decompress_one(c, codec, b), blobs))


__all__ = ["Codec", "ZlibCodec", "RawCodec", "LzmaCodec", "Bz2Codec",
           "RansCodec", "DEFAULT_CODEC", "AUTO_CODEC", "register_codec",
           "get_codec", "codec_names", "validate_codec_id", "choose_codec",
           "choose_block_codecs", "resolve_codec", "compress_blocks",
           "compress_blocks_per_codec", "decompress_block",
           "decompress_blocks"]
