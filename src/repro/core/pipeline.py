"""Shared NUMARCK pipeline stages: analyze -> encode -> finalize.

Both drivers -- ``core.compress`` (single device) and
``distributed.pipeline`` (shard_map) -- used to reimplement the host half
of the pipeline: center computation, exception compaction, per-block
entropy coding and blob assembly.  This module is the single home of those
stages, following the stage-structured design of arXiv:1903.07761 (and
LCP, arXiv:2411.00761): a driver produces an :class:`EncodedIndices`
(device work) and everything after that is shared, so the two paths emit
byte-identical ``CompressedStep`` blobs by construction.

Stage map:

  analyze   device  ratios, global range, histogram, auto-B   (per driver)
  encode    device  rank-LUT indexing + bit-packing           (per driver)
  finalize  host    exceptions, entropy stage, blob assembly  (HERE)

The finalize entropy stage is the pluggable parallel codec dispatcher in
``core.entropy``; the codec id is recorded on the step and persisted by
the NCK container.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import numpy as np

from repro.core import entropy, packing
from repro.core.types import CompressedStep, NumarckParams
from repro.obs import telemetry


class StepMeta(dict):
    """Step metadata dict with the deprecated ``"zlib_ratio"`` alias.

    ``"zlib_ratio"`` predates the pluggable entropy registry; the stage
    ratio has been codec-agnostic ``"entropy_ratio"`` since the registry
    landed.  Reading the alias warns once per process and keeps working.
    """

    _warned = False

    @classmethod
    def _warn_alias(cls):
        if not cls._warned:
            cls._warned = True
            warnings.warn(
                "meta['zlib_ratio'] is deprecated: the entropy stage is "
                "codec-pluggable; read meta['entropy_ratio'] instead",
                DeprecationWarning, stacklevel=4)

    def __getitem__(self, key):
        if key == "zlib_ratio":
            self._warn_alias()
        return dict.__getitem__(self, key)

    def get(self, key, default=None):
        if key == "zlib_ratio":
            self._warn_alias()
        return dict.get(self, key, default)


def reconstruction_dtype(dtype) -> np.dtype:
    """Arithmetic precision of the reconstruction R_i = R_{i-1}*(1+c).

    Reconstruction runs in the *source* precision -- float64 data in
    float64, everything else in float32 -- so the host chain, the device
    chain (Pallas or gather lowering) and every decompressor produce
    bit-identical state.  Sub-f32 dtypes still compute in f32 (their
    epsilon is comparable to typical error bounds) and round once at the
    end, exactly like every path does.
    """
    dt = np.dtype(dtype)
    return np.dtype(np.float64) if dt == np.float64 else np.dtype(np.float32)


def block_slices(n: int, block_elems: int) -> List[Tuple[int, int]]:
    return [(s, min(s + block_elems, n)) for s in range(0, n, block_elems)]


@dataclass
class EncodedIndices:
    """Driver-produced encode output: the contract between encode/finalize.

    ``packed`` holds the raw (pre-entropy) packed bytes of every index
    block in global order; the final block is marker-padded to the full
    ``block_elems`` so host and device packers emit identical streams.

    ``entropy_coded`` is the already-entropy-coded variant of that
    contract: drivers with a device entropy stage (kernels.rans) hand
    finalize the finished per-block blobs (+ the codec that made them)
    and finalize skips the host entropy stage entirely.

    ``exc_positions``/``exc_block_counts`` carry the device-computed
    exception compaction (kernels.ops.exception_compact): finalize
    gathers the incompressible values by position instead of re-scanning
    the full index table with a host boolean mask.  A driver that also
    gathered the values on the device sets ``exc_values`` with the
    step's ``dtype`` and ``shape``; finalize then never reads ``curr``.
    """

    # (n,) int32 bin ranks, marker = 2**B - 1.  May be None when the
    # driver entropy-coded and exception-compacted on device AND nothing
    # host-side (host reference chain) will read the table -- set ``n``
    # then, so finalize never forces a device->host fetch of it.
    idx: Optional[np.ndarray]
    b_bits: int
    block_elems: int
    n: Optional[int] = None    # element count; defaults to idx.size
    # Raw packed bytes per block.  Sharded driver fills this from the
    # device bit-pack kernel; None defers packing to the finalize stage
    # (host packer), which lets the overlapped stream keep the device
    # critical path free of host byte work.
    packed: Optional[List[bytes]] = None
    # Already-entropy-coded blocks (device entropy stage) + their codec.
    entropy_coded: Optional[List[bytes]] = None
    entropy_codec: Optional[str] = None
    # Device-compacted exceptions: ascending marker positions + per-block
    # marker counts (int64).  None => finalize falls back to the host scan.
    exc_positions: Optional[np.ndarray] = None
    exc_block_counts: Optional[np.ndarray] = None
    # The values at ``exc_positions`` in the source dtype, gathered on the
    # device, with the source array's dtype and shape.  None => finalize
    # reads them from ``curr``.
    exc_values: Optional[np.ndarray] = None
    dtype: Optional[np.dtype] = None
    shape: Optional[Tuple[int, ...]] = None

    @property
    def marker(self) -> int:
        return (1 << self.b_bits) - 1


@dataclass
class DeviceEncoded:
    """Output of the device analyze+encode stages (pre-entropy).

    ``idx_dev``/``curr_dev`` are optional device handles (jax.Array) of
    the index table and the current step, kept so a device-resident
    ReferenceChain can advance without a host round-trip.  ``curr_dev``
    uses the driver's own layout (the sharded driver hands over its
    padded, mesh-sharded f32 copy).  Host consumers only read ``enc``.
    """

    enc: EncodedIndices
    centers: np.ndarray          # rounded to the data dtype (float64 view)
    domain_lo: float
    width: float
    meta: dict
    idx_dev: Optional[Any] = None
    curr_dev: Optional[Any] = None


def topk_centers(ids_desc: np.ndarray, k_eff: int, domain_lo: float,
                 width: float) -> np.ndarray:
    """Bin centers of the top-k candidate bins (paper Eq. centre of bin)."""
    sel = np.asarray(ids_desc)[:k_eff]
    return (np.float64(domain_lo)
            + (sel.astype(np.float64) + 0.5) * np.float64(width))


def round_centers(centers: np.ndarray, dtype) -> np.ndarray:
    """Paper stores centers in the data's own float type (Fig. 2); round now
    so in-memory and from-file reconstructions agree bit-exactly."""
    return np.asarray(centers).astype(dtype).astype(np.float64)


def pack_blocks_host(idx: np.ndarray, b_bits: int,
                     block_elems: int) -> List[bytes]:
    """Host bit-pack stage: B-bit indices -> raw bytes per block.

    The final partial block is padded with markers so every block packs to
    the same byte length (mirrors the device packer; decompressors only
    read the valid prefix).

    One vectorized ``np.packbits`` over the marker-padded table, sliced at
    block boundaries: every block spans a whole number of bytes
    (block_elems is a multiple of 32, so block_elems * B is divisible by
    8), hence packing the concatenation equals packing each block alone --
    byte-identical to the per-block loop it replaced (asserted in
    tests/test_rans.py).
    """
    marker = (1 << b_bits) - 1
    n = idx.size
    if n == 0:
        return []
    with telemetry.span("finalize.pack", n=n, b_bits=b_bits):
        nblocks = -(-n // block_elems)
        total = nblocks * block_elems
        padded = idx if total == n else np.concatenate(
            [idx, np.full(total - n, marker, idx.dtype)])
        packed = packing.pack_indices_np(padded, b_bits).tobytes()
        bpb = block_elems * b_bits // 8          # bytes per block (exact)
        return [packed[s:s + bpb] for s in range(0, nblocks * bpb, bpb)]


def exception_offsets(incomp_mask: np.ndarray,
                      block_elems: int) -> np.ndarray:
    """Exclusive per-block prefix of incompressible counts (the
    decompressor's MPI_Scan analogue, done on host metadata)."""
    n = incomp_mask.size
    per_block = np.add.reduceat(incomp_mask,
                                np.arange(0, n, block_elems)).astype(np.int64)
    return np.concatenate([[0], np.cumsum(per_block)])[:-1]


def exception_table(idx: np.ndarray, marker: int, block_elems: int,
                    curr_flat: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Compact incompressible values + their per-block offset table."""
    incomp_mask = idx == marker
    return curr_flat[incomp_mask], exception_offsets(incomp_mask, block_elems)


def entropy_ratio(blobs: List[bytes], raw_sizes: np.ndarray) -> float:
    """Average entropy-stage compression ratio (paper Table 9)."""
    comp = sum(len(b) for b in blobs)
    return float(np.asarray(raw_sizes).sum()) / max(comp, 1)


def _primary_codec(block_codecs: List[str]) -> str:
    """Most common per-block codec (deterministic: ties break by name);
    recorded as the step-level codec field alongside the per-block ids."""
    counts: dict = {}
    for c in block_codecs:
        counts[c] = counts.get(c, 0) + 1
    return max(sorted(counts), key=lambda c: counts[c])


def finalize_step(curr: np.ndarray, enc: EncodedIndices,
                  centers: np.ndarray, domain_lo: float, width: float,
                  params: NumarckParams,
                  meta: Optional[dict] = None) -> CompressedStep:
    """Shared host finalize: exceptions, parallel entropy stage, assembly.

    Single-device and sharded drivers both land here, so their output
    blobs are byte-identical for identical encode results.

    Exceptions: when the encode stage compacted them on device
    (``enc.exc_positions``), finalize gathers the k values by position --
    the full index table is never re-scanned here; when it gathered the
    values too (``enc.exc_values``), ``curr`` is not read and may be
    None.  Entropy: when the
    encode stage already entropy-coded the blocks on device
    (``enc.entropy_coded``), finalize consumes the blobs as-is; otherwise
    the host codec stage runs, per-block adaptive under ``codec="auto"``
    (a codec id per block, persisted by the NCK container).
    """
    if enc.exc_values is None:
        curr = np.asarray(curr)
        dtype, shape = curr.dtype, tuple(curr.shape)
    else:
        dtype, shape = np.dtype(enc.dtype), tuple(enc.shape)
    n = int(enc.n if enc.n is not None else enc.idx.size)
    # Driver-side stage timings (encode_device/_device_encode attach them
    # when telemetry is enabled); never persisted into blob bytes -- the
    # NCK container stores `info` attrs, not `meta`.
    meta = dict(meta or {})
    drv_tele = meta.pop("telemetry", None) or {}
    with telemetry.span("finalize", n=n, b_bits=enc.b_bits) as sp_fin:
        with telemetry.span("finalize.exceptions") as sp_exc:
            if enc.exc_positions is not None:
                incomp_values = (
                    enc.exc_values if enc.exc_values is not None
                    else curr.reshape(-1)[enc.exc_positions])
                incomp_off = np.concatenate(
                    [[0],
                     np.cumsum(enc.exc_block_counts)])[:-1].astype(np.int64)
            else:
                incomp_values, incomp_off = exception_table(
                    enc.idx, enc.marker, enc.block_elems, curr.reshape(-1))

        block_codecs: Optional[List[str]] = None
        with telemetry.span("finalize.entropy") as sp_ent:
            if enc.entropy_coded is not None:
                blks = enc.entropy_coded
                codec = enc.entropy_codec or entropy.DEFAULT_CODEC
                bpb = enc.block_elems * enc.b_bits // 8
                raw_sizes = np.full(len(blks), bpb, np.int64)
            else:
                raws = (enc.packed if enc.packed is not None
                        else pack_blocks_host(enc.idx, enc.b_bits,
                                              enc.block_elems))
                raw_sizes = np.asarray([len(r) for r in raws], np.int64)
                if params.codec == entropy.AUTO_CODEC and len(raws) > 1:
                    # Per-block adaptive pick; the step and the container
                    # record concrete ids only (one per block when they
                    # differ).
                    per = entropy.choose_block_codecs(raws,
                                                      params.zlib_level)
                    if len(set(per)) > 1:
                        codec = _primary_codec(per)
                        block_codecs = per
                        blks = entropy.compress_blocks_per_codec(
                            raws, per, level=params.zlib_level,
                            parallel=params.parallel_entropy)
                    else:
                        codec = per[0]
                        blks = entropy.compress_blocks(
                            raws, codec=codec, level=params.zlib_level,
                            parallel=params.parallel_entropy)
                else:
                    # "auto" on single-block payloads resolves per step,
                    # exactly as before; concrete ids pass through
                    # unchanged.
                    codec = entropy.resolve_codec(params.codec, raws,
                                                  params.zlib_level)
                    blks = entropy.compress_blocks(
                        raws, codec=codec, level=params.zlib_level,
                        parallel=params.parallel_entropy)
            sp_ent.set(codec=codec, blocks=len(blks))
        centers = round_centers(centers, dtype)
        if centers.size > enc.marker:
            centers = centers[:enc.marker]
        ratio = entropy_ratio(blks, raw_sizes)
        bytes_in = int(np.asarray(raw_sizes).sum())
        bytes_out = sum(len(b) for b in blks)
        sp_fin.set(codec=codec, bytes_in=bytes_in, bytes_out=bytes_out)
    # "entropy_ratio" is the stage ratio whatever the codec; "zlib_ratio"
    # is kept as a deprecated alias (StepMeta warns once on read).
    full_meta = StepMeta({"entropy_ratio": ratio, "zlib_ratio": ratio,
                          "entropy_codec": codec})
    full_meta.update(meta)
    if telemetry.enabled():
        # Canonical per-step rollup: one fixed key set whatever the driver
        # (single-device vs sharded) or overlap mode, so series rollups
        # diff structurally (obs.report.STEP_TELEMETRY_KEYS).
        device_entropy = enc.entropy_coded is not None
        full_meta["telemetry"] = {
            "analyze_s": float(drv_tele.get("analyze_s", 0.0)),
            "encode_s": float(drv_tele.get("encode_s", 0.0)),
            "exceptions_s": sp_exc.duration,
            "entropy_s": (float(drv_tele.get("device_entropy_s", 0.0))
                          if device_entropy else sp_ent.duration),
            "finalize_s": sp_fin.duration,
            "bytes_in": bytes_in, "bytes_out": bytes_out,
            "entropy_ratio": ratio, "codec": codec,
            "device_entropy": device_entropy,
        }
    return CompressedStep(
        n=n, shape=shape, dtype=str(dtype),
        b_bits=enc.b_bits, error_bound=params.error_bound,
        strategy=params.strategy, reference=params.reference,
        domain_lo=float(domain_lo), bin_width=float(width),
        centers=centers, block_elems=enc.block_elems, codec=codec,
        block_codecs=block_codecs,
        index_blocks=blks, index_block_nbytes=raw_sizes,
        incomp_values=incomp_values, incomp_block_offsets=incomp_off,
        meta=full_meta)


def finalize_anchor(arr: np.ndarray, params: NumarckParams) -> CompressedStep:
    """Lossless anchor through the same entropy stage (codec-aware)."""
    arr = np.asarray(arr)
    flat = arr.reshape(-1)
    block_elems = max(1, params.block_bytes // flat.dtype.itemsize)
    with telemetry.span("finalize.anchor", n=arr.size) as sp:
        raws = [flat[s:e].tobytes() for s, e in block_slices(flat.size,
                                                             block_elems)]
        codec = entropy.resolve_codec(params.codec, raws, params.zlib_level)
        blks = entropy.compress_blocks(raws, codec=codec,
                                       level=params.zlib_level,
                                       parallel=params.parallel_entropy)
        sp.set(codec=codec)
    meta: dict = {"kind": "anchor"}
    if telemetry.enabled():
        bytes_in = arr.size * flat.dtype.itemsize
        bytes_out = sum(len(b) for b in blks)
        meta["telemetry"] = {
            "analyze_s": 0.0, "encode_s": 0.0, "exceptions_s": 0.0,
            "entropy_s": sp.duration, "finalize_s": sp.duration,
            "bytes_in": bytes_in, "bytes_out": bytes_out,
            "entropy_ratio": bytes_in / max(bytes_out, 1), "codec": codec,
            "device_entropy": False,
        }
    return CompressedStep(
        n=arr.size, shape=tuple(arr.shape), dtype=str(arr.dtype),
        b_bits=0, error_bound=params.error_bound, strategy=params.strategy,
        reference=params.reference, domain_lo=0.0, bin_width=0.0,
        centers=np.zeros(0), block_elems=block_elems, codec=codec,
        index_blocks=blks, meta=meta)


def reconstruct_from_indices(prev: np.ndarray, enc: EncodedIndices,
                             centers: np.ndarray, dtype,
                             incomp_values: Optional[np.ndarray] = None,
                             curr: Optional[np.ndarray] = None) -> np.ndarray:
    """Reconstruct R_i from the *pre-entropy* encode result.

    This is what lets the overlapped temporal stream advance: the
    REF_RECONSTRUCTED chain needs R_i before compressing step i+1, but not
    the deflated blobs -- so the entropy stage of step i can run in the
    background while the device encodes step i+1.  Bit-identical to
    ``decompress_step`` on the finalized blob AND to the device-resident
    chain: arithmetic runs in ``reconstruction_dtype(dtype)`` (the source
    precision), never silently promoting f32 chains through float64.
    """
    marker = enc.marker
    prev = np.asarray(prev)
    cdt = reconstruction_dtype(dtype)
    prev_flat = prev.reshape(-1).astype(cdt, copy=False)
    centers = np.asarray(centers, np.float64).astype(cdt)
    lut = np.concatenate([centers, np.zeros(marker + 1 - centers.size,
                                            cdt)])
    out = prev_flat * (1 + lut[enc.idx])
    mask = enc.idx == marker
    if mask.any():
        if incomp_values is None:
            assert curr is not None
            incomp_values = np.asarray(curr).reshape(-1)[mask]
        out[mask] = incomp_values.astype(cdt)
    return out.astype(dtype).reshape(prev.shape)


__all__ = ["StepMeta", "EncodedIndices", "DeviceEncoded", "block_slices",
           "topk_centers",
           "round_centers", "pack_blocks_host", "exception_offsets",
           "exception_table", "entropy_ratio", "finalize_step",
           "finalize_anchor", "reconstruct_from_indices",
           "reconstruction_dtype"]
