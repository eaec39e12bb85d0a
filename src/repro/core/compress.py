"""Single-device NUMARCK compress / decompress driver.

Device (jit) stages:
  1. `_analyze`     -- ratios, candidate histogram, descending sort, auto-B
  2. `_encode_topk` -- rank LUT + per-element index assignment (top-k)
     `_encode_centers` -- nearest-center assignment (equal/log/kmeans)
Host finalize is the *shared* stage in ``core.pipeline`` (exception
compaction, parallel entropy coding via the ``core.entropy`` codec
registry, blob assembly); the sharded driver
(``repro.distributed.pipeline``) lands in the same finalize, so the two
paths emit byte-identical blobs.

`TemporalCompressor(overlap=True)` / `compress_series(..., overlap=True)`
double-buffer the device/host split (paper Sec. IV-C I/O overlap): the
device analyze/encode of step i+1 runs while a background thread runs the
host entropy stage of step i.  The REF_RECONSTRUCTED chain is a
``core.chain.ReferenceChain``: device-resident by default (f32, or f64
under jax_enable_x64) so R_i never leaves the accelerator between steps,
host-resident (``pipeline.reconstruct_from_indices``) otherwise --
byte-identical blobs either way.
"""
from __future__ import annotations

from collections import deque
from concurrent.futures import Future
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binning, blocks, entropy, ratios, select_b
from repro.core import chain as chainmod
from repro.core import pipeline as pipe
from repro.core.overlap import FinalizeQueue
from repro.core.pipeline import DeviceEncoded
from repro.faults.errors import IntegrityError
from repro.kernels import ops as kops
from repro.kernels import rans
from repro.obs import telemetry
from repro.core.types import (CompressedStep, NumarckParams,
                              REF_RECONSTRUCTED, STRATEGY_EQUAL,
                              STRATEGY_KMEANS, STRATEGY_LOG, STRATEGY_TOPK,
                              dtype_nbytes)


@partial(jax.jit, static_argnames=("max_bins", "b_max", "elem_bytes"))
def _analyze(prev, curr, error_bound, max_bins, b_max, elem_bytes):
    r, valid = ratios.change_ratios(prev, curr)
    lo, hi = ratios.ratio_range(r, valid)
    domain_lo, width = ratios.histogram_domain(lo, hi, error_bound, max_bins)
    bin_ids, ok = ratios.candidate_bin_ids(r, valid, domain_lo, width,
                                           max_bins)
    counts = binning.local_histogram(bin_ids, ok, max_bins)
    counts_desc, ids_desc = binning.sort_histogram(counts)
    b_auto, est_sizes = select_b.choose_b(counts_desc, r.shape[0], elem_bytes,
                                          b_max)
    return dict(ratios=r, valid=valid, bin_ids=bin_ids, counts=counts,
                counts_desc=counts_desc, ids_desc=ids_desc,
                domain_lo=domain_lo, width=width, b_auto=b_auto,
                est_sizes=est_sizes, lo=lo, hi=hi)


@partial(jax.jit, static_argnames=("b_bits", "k_eff", "max_bins"))
def _encode_topk(bin_ids, ids_desc, b_bits, k_eff, max_bins):
    marker = (1 << b_bits) - 1
    lut = binning.rank_lut(ids_desc[:k_eff], k_eff, max_bins)
    # rank_lut fills non-selected with k_eff; remap to the B-bit marker.
    ranks = lut[jnp.clip(bin_ids, 0, max_bins - 1)]
    ranks = jnp.where(ranks >= k_eff, marker, ranks)
    return jnp.where(bin_ids >= 0, ranks, marker).astype(jnp.int32)


@partial(jax.jit, static_argnames=("b_bits",))
def _encode_centers(r, valid, centers_sorted, error_bound, b_bits):
    marker = (1 << b_bits) - 1
    idx = binning.assign_nearest(r, valid, centers_sorted, error_bound)
    return jnp.where(idx >= centers_sorted.shape[0], marker, idx)


def make_anchor(arr: np.ndarray, params: NumarckParams) -> CompressedStep:
    """Losslessly stored first iteration (no previous step to diff against).

    Stored in entropy-coded *blocks* like the index table so that partial
    decompression works from iteration 0 onwards.
    """
    return pipe.finalize_anchor(arr, params)


def decode_anchor(step: CompressedStep) -> np.ndarray:
    """Host reconstruction of a losslessly stored anchor step.  When the
    step qualifies for the device decode route the entropy stage runs as
    one block-group-parallel device scan (``decode_bytes_blocks_device``)
    and only the finished bytes cross back; otherwise the host codec
    registry inflates the blocks (pool-parallel)."""
    tele = telemetry.enabled()
    with telemetry.span("decode.entropy") as sp_e:
        if device_decode_route(step):
            flat = rans.decode_bytes_blocks_device(
                step.index_blocks, pool=entropy._shared_pool())
            raw = np.asarray(flat).tobytes()
        else:
            raw = b"".join(entropy.decompress_blocks(step.index_blocks,
                                                     step.codec))
    try:
        out = np.frombuffer(raw, dtype=step.dtype).reshape(step.shape).copy()
    except ValueError as e:
        # Blocks inflated "successfully" but to the wrong total size:
        # corruption the codec stream itself could not detect.
        raise IntegrityError(
            f"anchor decode produced {len(raw)} bytes, expected "
            f"{step.n * np.dtype(step.dtype).itemsize} for shape "
            f"{tuple(step.shape)} {step.dtype} ({e}) -- payload corrupt "
            "or truncated") from e
    if tele:
        _record_read(step, entropy_s=sp_e.duration,
                     device=device_decode_route(step))
    return out


def decode_anchor_device(step: CompressedStep) -> jax.Array:
    """Anchor decode that leaves the reconstruction on device (serve-tier
    session restore).  The entropy stage decodes on device and the bytes
    bitcast in place to ``step.dtype`` when the device can hold it
    bit-exactly; otherwise this falls back to the host decode plus one
    upload -- the result is identical either way."""
    dt = np.dtype(step.dtype)
    device_ok = (dt in (np.dtype(np.float32), np.dtype(np.int32),
                        np.dtype(np.uint32))
                 or (dt.itemsize == 8 and jax.config.jax_enable_x64))
    if not (device_ok and device_decode_route(step)):
        return jnp.asarray(decode_anchor(step))
    tele = telemetry.enabled()
    with telemetry.span("decode.entropy") as sp_e:
        flat = rans.decode_bytes_blocks_device(
            step.index_blocks, pool=entropy._shared_pool())
        out = jax.lax.bitcast_convert_type(
            flat.reshape(-1, dt.itemsize), dt).reshape(step.shape)
        if tele:
            jax.block_until_ready(out)
    if tele:
        _record_read(step, entropy_s=sp_e.duration, device=True)
    return out


def device_decode_route(step: CompressedStep) -> bool:
    """Route a read through the device decode pipeline?  The
    reconstruction is bit-identical either way (same IEEE ops, same
    blobs), so -- like ``device_entropy_route`` -- this is purely a
    wall-clock decision: homogeneous device-codec blocks and a payload
    big enough to amortize dispatch."""
    if step.block_codecs is not None:
        return False
    try:
        codec = entropy.get_codec(step.codec)
    except ValueError:
        return False
    if not codec.device:
        return False
    if step.is_anchor:
        nbytes = step.n * np.dtype(step.dtype).itemsize
    else:
        cdt = pipe.reconstruction_dtype(step.dtype)
        if cdt == np.float64 and not jax.config.jax_enable_x64:
            return False
        nbytes = step.n * step.b_bits // 8
    return nbytes >= rans.DEVICE_MIN_BYTES


def symbol_entropy_route(params: NumarckParams, b_bits: int,
                         k_eff: int) -> bool:
    """Use the symbol-level (v2/NCK3) coder for this step's blocks?
    Top-k only: the analyze stage's ``counts_desc`` is the exact global
    rank histogram there, and the dense {rank, marker} alphabet must fit
    the frequency budget (k_eff + 1 <= 2^SCALE_BITS)."""
    return (params.symbol_rans and params.strategy == STRATEGY_TOPK
            and k_eff + 1 <= rans.M)


def device_entropy_route(params: NumarckParams, n: int, b_bits: int) -> bool:
    """Route the entropy stage to the codec's device encoder?  Blobs are
    byte-identical either way; this is purely a wall-clock decision, so
    small payloads stay on the (cheaper-to-dispatch) host path."""
    if not params.device_entropy or params.codec == entropy.AUTO_CODEC:
        return False
    try:
        codec = entropy.get_codec(params.codec)
    except ValueError:
        return False
    return codec.device and n * b_bits // 8 >= rans.DEVICE_MIN_BYTES


def encode_device(prev, curr, params: NumarckParams,
                  need_host_idx: bool = True) -> DeviceEncoded:
    """Device stages for one step: analyze + strategy dispatch + indexing.

    `prev`/`curr` may be host ndarrays or device jax.Arrays (a
    device-resident ReferenceChain feeds its state straight back in
    without a host copy); the returned ``DeviceEncoded`` carries device
    handles of the index table and `curr` for the chain advance.

    ``need_host_idx=False`` (callers whose reference chain is
    device-resident) skips the host fetch of the index table when the
    device entropy stage also ran -- finalize then reads only the
    pre-compressed blobs and the compacted exceptions, so nothing
    host-side ever touches the table.
    """
    # Host-ndarray inputs are normalized in place -- no device round-trip.
    if not isinstance(prev, jax.Array):
        prev = np.asarray(prev)   # repro-lint: disable=host-sync-in-device-path
    if not isinstance(curr, jax.Array):
        curr = np.asarray(curr)   # repro-lint: disable=host-sync-in-device-path
    if prev.shape != curr.shape:
        raise ValueError("temporal steps must share a shape")
    ebytes = dtype_nbytes(curr.dtype)
    # Telemetry-enabled runs block after each device stage so span
    # durations mean "stage time", not "async dispatch time"; with
    # telemetry disabled dispatch stays fully asynchronous.
    tele = telemetry.enabled()
    with telemetry.span("encode.analyze") as sp_an:
        a = _analyze(prev.reshape(-1), curr.reshape(-1),
                     np.float32(params.error_bound), params.max_bins,
                     params.b_max, ebytes)
        if tele:
            jax.block_until_ready(a)

    with telemetry.span("encode.index", strategy=params.strategy) as sp_idx:
        if params.strategy == STRATEGY_TOPK:
            b_bits = int(params.b_bits if params.b_bits is not None
                         else a["b_auto"])
            k_eff = min((1 << b_bits) - 1, params.max_bins)
            idx = _encode_topk(a["bin_ids"], a["ids_desc"], b_bits, k_eff,
                               params.max_bins)
            centers = pipe.topk_centers(np.asarray(a["ids_desc"]), k_eff,
                                        float(a["domain_lo"]),
                                        float(a["width"]))
        else:
            b_bits = int(params.b_bits if params.b_bits is not None else 8)
            k_eff = (1 << b_bits) - 1
            if params.strategy == STRATEGY_EQUAL:
                cs = binning.equal_width_centers(a["lo"], a["hi"], k_eff)
            elif params.strategy == STRATEGY_LOG:
                cs = binning.log_scale_centers(a["ratios"], a["valid"],
                                               k_eff)
            elif params.strategy == STRATEGY_KMEANS:
                k_km = min(k_eff, params.kmeans_max_k)
                cs = binning.kmeans_centers(a["counts"], a["domain_lo"],
                                            a["width"], k_km,
                                            params.kmeans_iters)
            else:  # pragma: no cover
                raise ValueError(params.strategy)
            cs = jnp.sort(cs)
            idx = _encode_centers(a["ratios"], a["valid"], cs,
                                  np.float32(params.error_bound), b_bits)
            centers = np.asarray(cs, np.float64)
        if tele:
            jax.block_until_ready(idx)

    centers = pipe.round_centers(centers, curr.dtype)
    n = int(np.prod(curr.shape))
    be = params.block_elems(b_bits)
    marker = (1 << b_bits) - 1
    # Exception compaction on device: finalize gathers values by position
    # instead of re-scanning the index table with a host mask.
    exc_counts = exc_pos = None
    with telemetry.span("encode.exceptions") as sp_exc:
        if n:
            exc_counts, exc_pos = kops.exception_compact(idx, n, marker, be)
    # Device entropy stage: pack + rANS-code the blocks on device; the
    # finalize consumes the finished blobs (byte-identical to the host
    # codec flavor, so routing never changes the file format).
    coded = coded_name = None
    with telemetry.span("encode.device_entropy") as sp_de:
        if device_entropy_route(params, n, b_bits):
            nblocks = -(-n // be)
            idx_pad = jnp.pad(idx, (0, nblocks * be - n),
                              constant_values=marker)
            if symbol_entropy_route(params, b_bits, k_eff):
                counts_ranks = np.asarray(a["counts_desc"])[:k_eff]
                coded = rans.compress_blocks_device_symbols(
                    idx_pad, b_bits, k_eff, nblocks, be, counts_ranks,
                    pool=entropy._shared_pool())
            else:
                coded = rans.compress_blocks_device(
                    idx_pad, b_bits, nblocks, be,
                    pool=entropy._shared_pool())
            coded_name = params.codec
    with telemetry.span("encode.idx_fetch") as sp_fetch:
        # The one designed host fetch of the table; skipped entirely when
        # the caller's chain is device-resident (need_host_idx=False).
        # repro-lint: disable=host-sync-in-device-path
        idx_host = (np.asarray(idx) if need_host_idx or coded is None
                    else None)
    enc = pipe.EncodedIndices(idx=idx_host, b_bits=b_bits,
                              block_elems=be, n=n,
                              entropy_coded=coded, entropy_codec=coded_name,
                              exc_positions=exc_pos,
                              exc_block_counts=exc_counts)
    meta = {"b_auto": int(a["b_auto"]),
            "est_sizes": np.asarray(a["est_sizes"]).tolist(),
            "ratio_min": float(a["lo"]), "ratio_max": float(a["hi"])}
    if tele:
        # Driver stage timings; finalize_step folds them into the
        # canonical per-step meta["telemetry"] record and pops this dict,
        # so the key never reaches the persisted container attrs.
        meta["telemetry"] = {
            "analyze_s": sp_an.duration,
            "encode_s": (sp_idx.duration + sp_exc.duration
                         + sp_fetch.duration),
            "device_entropy_s": sp_de.duration,
        }
    return DeviceEncoded(enc=enc, centers=centers,
                         domain_lo=float(a["domain_lo"]),
                         width=float(a["width"]), meta=meta,
                         idx_dev=idx,
                         curr_dev=curr if isinstance(curr, jax.Array)
                         else None)


def compress_step(prev: np.ndarray, curr: np.ndarray,
                  params: NumarckParams) -> CompressedStep:
    """Compress `curr` against the reference state `prev` (Eq. 1/4).

    `prev` is the original previous iteration in REF_ORIGINAL mode, or the
    previously *reconstructed* state in REF_RECONSTRUCTED mode (the
    TemporalCompressor picks the right one).
    """
    dev = encode_device(prev, curr, params, need_host_idx=False)
    return pipe.finalize_step(curr, dev.enc, dev.centers, dev.domain_lo,
                              dev.width, params, dev.meta)


def _record_read(step: CompressedStep, entropy_s: float = 0.0,
                 dequant_s: float = 0.0, patch_s: float = 0.0,
                 fetch_s: float = 0.0, device: bool = False) -> None:
    """Fold the decode-side span durations into the canonical per-read
    telemetry record (``obs.report.READ_TELEMETRY_KEYS``), identical
    across the single-device, sharded, and anchor read paths."""
    from repro.obs import report
    rec = {"entropy_s": entropy_s, "dequant_s": dequant_s,
           "patch_s": patch_s, "fetch_s": fetch_s,
           "bytes_in": int(sum(len(b) for b in step.index_blocks)),
           "bytes_out": int(step.n) * np.dtype(step.dtype).itemsize,
           "codec": step.codec, "device_decode": bool(device)}
    assert tuple(rec) == report.READ_TELEMETRY_KEYS
    step.meta["telemetry_read"] = rec


def _decode_index_host(step: CompressedStep) -> np.ndarray:
    """Inflate every index block of a step into one preallocated (n,)
    int32 buffer, block-parallel over the shared entropy pool for
    payloads worth the dispatch."""
    idx = np.empty(step.n, np.int32)
    slices = list(blocks.block_slices(step.n, step.block_elems))

    def inflate(bi: int) -> None:
        s, e = slices[bi]
        idx[s:e] = blocks.inflate_block(step.index_blocks[bi], e - s,
                                        step.b_bits,
                                        codec=step.codec_for_block(bi))

    payload = sum(len(b) for b in step.index_blocks)
    if len(slices) > 1 and payload >= entropy._MIN_PARALLEL_BYTES:
        list(entropy._shared_pool().map(inflate, range(len(slices))))
    else:
        for bi in range(len(slices)):
            inflate(bi)
    return idx


def _centers_lut(step: CompressedStep, cdt) -> np.ndarray:
    marker = (1 << step.b_bits) - 1
    return np.concatenate([step.centers,
                           np.zeros(marker + 1 - step.centers.size)
                           ]).astype(cdt)


def decompress_step_device(step: CompressedStep, prev) -> jax.Array:
    """Device-resident reconstruction of one delta step: blob -> device
    rANS decode -> fused dequantize -> exception patch, zero host round
    trips.  ``prev`` may be a host ndarray or a device array (the
    device-resident decompressor chain feeds its state straight back).
    Returns the reconstruction as a (step.shape) device array of the
    source dtype; bit-identical to the host ``decompress_step`` by the
    same argument as the encode side (same IEEE ops on the same data).
    """
    assert prev is not None, "non-anchor steps need the previous state"
    tele = telemetry.enabled()
    cdt = pipe.reconstruction_dtype(step.dtype)
    with telemetry.span("decode.entropy") as sp_e:
        idx2d = rans.decode_blocks_device(step.index_blocks, step.b_bits,
                                          step.block_elems,
                                          pool=entropy._shared_pool())
        idx = idx2d.reshape(-1)[:step.n]
        if tele:
            jax.block_until_ready(idx)
    with telemetry.span("decode.dequant") as sp_d:
        prev_dev = jnp.asarray(prev).reshape(-1).astype(cdt)
        centers = jnp.asarray(_centers_lut(step, cdt))
        recon = kops.dequantize(idx, prev_dev, centers, b_bits=step.b_bits,
                                use_pallas=not kops._interpret())
        if tele:
            jax.block_until_ready(recon)
    with telemetry.span("decode.patch") as sp_p:
        if step.n_incompressible:
            recon = kops.patch_exceptions(recon, idx,
                                          jnp.asarray(step.incomp_values),
                                          b_bits=step.b_bits)
        out = recon.astype(step.dtype).reshape(step.shape)
        if tele:
            jax.block_until_ready(out)
    if tele:
        _record_read(step, entropy_s=sp_e.duration, dequant_s=sp_d.duration,
                     patch_s=sp_p.duration, device=True)
    return out


def decompress_step(step: CompressedStep,
                    prev: Optional[np.ndarray]) -> np.ndarray:
    """Reconstruct R_i = R_{i-1} * (1 + center)  (corrected Eq. 4).

    Arithmetic runs in the step's source precision
    (``pipeline.reconstruction_dtype``) so the replayed chain is
    bit-identical to the compressor's reference chain, host- or
    device-resident, for float32 and float64 data alike.  Steps that
    qualify for the device decode route (``device_decode_route``) run
    blob -> device rANS decode -> fused dequantize -> exception patch
    with one final fetch; everything else takes the pool-parallel host
    path.  Results are bit-identical across routes.
    """
    if step.is_anchor:
        return decode_anchor(step)
    if device_decode_route(step):
        dev = decompress_step_device(step, prev)
        with telemetry.span("decode.fetch") as sp_f:
            out = np.asarray(dev)
        if telemetry.enabled() and "telemetry_read" in step.meta:
            step.meta["telemetry_read"]["fetch_s"] = sp_f.duration
        return out
    assert prev is not None, "non-anchor steps need the previous state"
    tele = telemetry.enabled()
    cdt = pipe.reconstruction_dtype(step.dtype)
    marker = (1 << step.b_bits) - 1
    with telemetry.span("decode.entropy") as sp_e:
        idx = _decode_index_host(step)
    with telemetry.span("decode.dequant") as sp_d:
        prev_flat = np.asarray(prev).reshape(-1).astype(cdt, copy=False)
        centers = _centers_lut(step, cdt)
        out = prev_flat * (1 + centers[idx])
    with telemetry.span("decode.patch") as sp_p:
        if step.n_incompressible:
            # Exception values are compacted in stream order == block
            # order, so one global boolean scatter equals the per-block
            # patch loop.
            out[idx == marker] = step.incomp_values.astype(cdt)
    if tele:
        _record_read(step, entropy_s=sp_e.duration, dequant_s=sp_d.duration,
                     patch_s=sp_p.duration, device=False)
    return out.astype(step.dtype).reshape(step.shape)


class TemporalCompressor:
    """Streaming compressor over a temporal series (paper Sec. III).

    With ``overlap=True`` the host finalize of step i (entropy stage +
    blob assembly) runs on a background thread while the caller's next
    ``add``/``add_async`` drives the device encode of step i+1.  Results
    are identical to the serial path; only wall-clock changes.

    ``chain`` picks the residency of the prev->recon reference chain
    (``core.chain``): "auto" (default) keeps it device-resident whenever
    the device can hold the dtype bit-exactly, "host" forces the original
    NumPy chain, "device" forces the accelerator chain.  Blobs are
    byte-identical across residencies.
    """

    def __init__(self, params: NumarckParams = NumarckParams(),
                 overlap: bool = False, chain: str = chainmod.CHAIN_AUTO):
        if chain not in chainmod.RESIDENCIES:
            raise ValueError(f"unknown chain residency {chain!r}")
        self.params = params
        self.overlap = overlap
        self.chain = chain
        self._chain: Optional[chainmod.ReferenceChain] = None
        # Bounded at two in-flight finalizes (one executing + one queued),
        # so direct add_async callers get the same ~2-step host-memory
        # bound as compress_series / the sharded driver.
        self._q = FinalizeQueue(overlap)
        self._step = 0

    def add_async(self, arr: np.ndarray) -> "Future[CompressedStep]":
        """Device-encode `arr` now; return a future of the finalized step.

        The internal reference chain advances before returning, so the
        next call may be issued immediately.
        """
        arr = np.asarray(arr)
        step_i, self._step = self._step, self._step + 1
        if self._chain is None or self._chain.empty:
            self._chain = chainmod.make_reference_chain(self.chain,
                                                        arr.dtype)
            self._chain.seed(arr)
            return self._q.submit(pipe.finalize_anchor, arr.copy(),
                                  self.params,
                                  label=f"anchor step {step_i}")
        # One H2D of `curr`, reused by both the encode and the chain
        # advance when the chain lives on device.  jnp.array (a private
        # copy, never a zero-copy alias): the chain advance reads it
        # asynchronously after add_async returns, and callers are allowed
        # to reuse their buffers immediately.
        curr_in = (jnp.array(arr)
                   if self._chain.residency == chainmod.CHAIN_DEVICE
                   else arr)
        dev = encode_device(
            self._chain.peek(), curr_in, self.params,
            need_host_idx=self._chain.residency == chainmod.CHAIN_HOST)
        if self.params.reference == REF_RECONSTRUCTED:
            self._chain.advance(dev, arr)
        else:
            self._chain.replace(arr)
        # The background finalize reads `arr` (exception values); snapshot
        # it so callers may reuse/mutate their buffer immediately.
        curr = arr.copy() if self.overlap else arr
        return self._q.submit(pipe.finalize_step, curr, dev.enc,
                              dev.centers, dev.domain_lo, dev.width,
                              self.params, dev.meta,
                              label=f"finalize step {step_i}")

    def add(self, arr: np.ndarray) -> CompressedStep:
        return self.add_async(arr).result()

    def reference_state(self) -> Optional[np.ndarray]:
        """Host copy of the current chain state (None before the anchor).
        This is the only place the device-resident chain crosses to host;
        the hot loop never does."""
        if self._chain is None or self._chain.empty:
            return None
        return self._chain.to_host()

    def flush(self):
        """Block until every in-flight finalize has completed (re-raises
        the first background exception, if any)."""
        self._q.flush()

    def close(self):
        self._q.close()

    def reset(self):
        self._chain = None
        self._step = 0


class TemporalDecompressor:
    """Streaming decompressor; mirrors TemporalCompressor state chaining.

    When consecutive steps qualify for the device decode route the chain
    state stays device-resident between steps (the next step's dequantize
    reads it without an upload); ``add`` still returns a host ndarray.
    Mixed routes are fine -- the state crosses the boundary at most once
    per route switch, and reconstructions are bit-identical throughout
    (the state round-trips through the source dtype each step on both
    routes).
    """

    def __init__(self):
        self._state = None          # np.ndarray or device jax.Array

    def add(self, step: CompressedStep) -> np.ndarray:
        if not step.is_anchor and device_decode_route(step):
            self._state = decompress_step_device(step, self._state)
            with telemetry.span("decode.fetch") as sp_f:
                out = np.asarray(self._state)
            if telemetry.enabled() and "telemetry_read" in step.meta:
                step.meta["telemetry_read"]["fetch_s"] = sp_f.duration
            return out
        prev = (np.asarray(self._state)
                if isinstance(self._state, jax.Array) else self._state)
        self._state = decompress_step(step, prev)
        return self._state

    def reset(self):
        self._state = None


def compress_series(arrays, params: NumarckParams = NumarckParams(),
                    overlap: bool = False,
                    chain: str = chainmod.CHAIN_AUTO) -> List[CompressedStep]:
    """Compress a temporal series; ``overlap=True`` double-buffers the
    device encode of step i+1 against the host finalize of step i.

    At most two finalizes are in flight at once, so host memory stays
    bounded at ~2 steps regardless of series length.
    """
    c = TemporalCompressor(params, overlap=overlap, chain=chain)
    out: List[CompressedStep] = []
    pending: deque = deque()
    try:
        for a in arrays:
            pending.append(c.add_async(a))
            while len(pending) > 2:
                out.append(pending.popleft().result())
        out.extend(f.result() for f in pending)
        return out
    finally:
        c.close()


def decompress_series(steps: List[CompressedStep]) -> List[np.ndarray]:
    d = TemporalDecompressor()
    return [d.add(s) for s in steps]


__all__ = ["compress_step", "decompress_step", "decompress_step_device",
           "make_anchor", "decode_anchor", "decode_anchor_device",
           "encode_device", "device_entropy_route", "device_decode_route",
           "symbol_entropy_route", "DeviceEncoded",
           "TemporalCompressor", "TemporalDecompressor", "compress_series",
           "decompress_series"]
