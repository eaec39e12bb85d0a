"""Sharded NUMARCK compression pipeline (paper Sec. IV, shard_map version).

Phases and their parallelization, 1:1 with the paper:

  1. change-ratio calculation  -- local Pallas kernel; pmin/pmax for the
     global range (MPI_Allreduce analogue).
  2. bin construction (top-k)  -- local Pallas histogram; lax.psum merges
     (MPI_Allreduce); every shard runs the same top-k sort + Eq. (6) B scan
     (replicated "serial part", Table 3).
  3. indexing                  -- local rank-LUT lookup.
  4. index alignment           -- block boundaries are *static* under the
     even distribution both we and the paper assume; the straddling block is
     completed by a fixed-width lax.ppermute edge exchange (MPI_Send/Recv
     analogue, <= 1 block like the paper's <= 2 MB).
  5. bits packing              -- local Pallas kernel over owned blocks.
  6. entropy coding + write    -- host stage (not a TPU workload; the paper
     also runs it on the CPU cores).  Shared with the single-device driver:
     `core.pipeline.finalize_step` dispatches the pluggable codec
     (`core.entropy`) over a thread pool.

B must be static for bit-packing, so the pipeline is two jitted stages:
`analyze` (histogram -> auto-B) and `encode` (indices -> packed blocks).
Both stages are jit-cached per (shape, B) signature so a temporal series
traces once and replays, and with ``overlap=True`` the host finalize
(exceptions + entropy + assembly) of step i runs on a background thread
while the caller drives the device encode of step i+1 -- the sharded
version of the paper's Sec. IV-C compute/IO overlap (at 12800 ranks the
entropy+write stage is exactly where NUMARCK's wall-clock hides).

The temporal reference chain (REF_RECONSTRUCTED) is mesh-resident by
default: a third jit-cached shard_map stage reuses the `_decode_shard`
dequantize kernel plus an on-device exception patch from the current
step, so between-step state stays sharded on the devices instead of
round-tripping through host `reconstruct_from_indices` every step.
Byte-identical to the host chain (``chain="host"``) by construction --
reconstruction arithmetic runs in the source precision on both paths.
"""
from __future__ import annotations

from collections import deque
from concurrent.futures import Future
from functools import partial, update_wrapper
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core import binning, entropy, packing, ratios, select_b
from repro.core import chain as chainmod
from repro.core import pipeline as pipe
from repro.core.container import ShardNCKWriter, StepFragment
from repro.core.compress import decompress_step, device_entropy_route
from repro.core.overlap import FinalizeQueue
from repro.core.pipeline import DeviceEncoded
from repro.core.types import (CompressedStep, NumarckParams,
                              REF_RECONSTRUCTED)
from repro.distributed import collectives as coll
from repro.faults import inject
from repro.kernels import dequant
from repro.kernels import ops as kops
from repro.kernels import rans
from repro.obs import telemetry


def _pad_to(x: np.ndarray, total: int, value) -> np.ndarray:
    return np.pad(x, (0, total - x.size), constant_values=value)


def _put_sharded(arr: np.ndarray, sharding):
    """Host -> device upload honoring `sharding`, multi-process safe:
    under a multi-process mesh only this process's addressable shards
    materialize (make_array_from_callback); every process holds the same
    host array (SPMD input), so the global array is consistent without
    any cross-process transfer."""
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def _relayout(x, *, total):
    """A field as any sharding holds it -> the flat, zero-padded layout of
    the encode stages (C order; the out sharding places each shard)."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, total - flat.size))


# jit cache of `_gather_shard` stages, keyed by (mesh, axis, ln, size).
_GATHER_FNS: Dict[Tuple, object] = {}


def _gather_shard(x_l, pos, *, ln, axis):
    """Per-shard gather of the values at global positions ``pos``
    (replicated): each shard takes the positions it holds and the shards'
    bit patterns are summed, so every value arrives exactly (a float sum
    would turn -0.0 into 0.0)."""
    s = jax.lax.axis_index(axis).astype(pos.dtype)
    local = pos - s * ln
    mine = (local >= 0) & (local < ln)
    v = x_l[jnp.clip(local, 0, ln - 1)]
    if v.dtype.itemsize == 2:
        # Summed in int32 and narrowed back exactly.
        bits = jax.lax.bitcast_convert_type(v, jnp.int16).astype(jnp.int32)
    else:
        # int32 words; an 8-byte value becomes a trailing pair of them.
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    mine = mine.reshape(mine.shape + (1,) * (bits.ndim - 1))
    bits = jax.lax.psum(jnp.where(mine, bits, 0), axis)
    if v.dtype.itemsize == 2:
        bits = bits.astype(jnp.int16)
    return jax.lax.bitcast_convert_type(bits, v.dtype)


def _analyze_shard(prev_l, curr_l, error_bound, *, max_bins, b_max,
                   elem_bytes, n_total, axis, use_pallas,
                   fixed_domain=False):
    """Per-shard phase 1+2: ratios, local histogram, global reduce, auto-B."""
    if fixed_domain:
        # SS Perf: skip the range pass entirely -- one fewer full read of
        # prev/curr and no phase-1 Allreduce (NumarckParams.fixed_domain)
        width = jnp.float32(2.0 * error_bound)
        domain_lo = -0.5 * width * max_bins
        lo = domain_lo
        hi = -domain_lo
    else:
        r, valid = ratios.change_ratios(prev_l, curr_l)
        lo_l = jnp.min(jnp.where(valid, r, jnp.inf))
        hi_l = jnp.max(jnp.where(valid, r, -jnp.inf))
        lo, hi = coll.allreduce_minmax(lo_l, hi_l, axis)  # MPI_Allreduce
        any_valid = coll.allreduce_sum(valid.sum(), axis) > 0
        lo = jnp.where(any_valid & jnp.isfinite(lo), lo, 0.0)
        hi = jnp.where(any_valid & jnp.isfinite(hi), hi, 0.0)
        domain_lo, width = ratios.histogram_domain(lo, hi, error_bound,
                                                   max_bins)
    _, bin_ids = kops.change_ratio_bins(prev_l, curr_l, domain_lo, width,
                                        max_bins=max_bins,
                                        use_pallas=use_pallas)
    hist_l = kops.histogram(bin_ids, max_bins=max_bins,
                            use_pallas=use_pallas)
    hist = coll.allreduce_sum(hist_l, axis)          # MPI_Allreduce(SUM)
    counts_desc, ids_desc = binning.sort_histogram(hist)
    b_auto, est_sizes = select_b.choose_b(counts_desc, n_total, elem_bytes,
                                          b_max)
    # Post-allreduce metadata is identical on every shard; replicated
    # (P()) out_specs make it host-fetchable on EVERY process of a
    # multi-process mesh (a P(axis) output's np.asarray would need a
    # cross-process gather, which jax rightly refuses).
    return (b_auto, ids_desc, counts_desc, domain_lo, width, est_sizes)


def _encode_shard(prev_l, curr_l, ids_desc, domain_lo, width, *, b_bits,
                  k_eff, max_bins, block_elems, ln, n_total, axis,
                  use_pallas):
    """Per-shard phase 3-5: index, align (ppermute), pack (Pallas)."""
    marker = (1 << b_bits) - 1
    _, bin_ids = kops.change_ratio_bins(prev_l, curr_l, domain_lo,
                                        width, max_bins=max_bins,
                                        use_pallas=use_pallas)
    lut = binning.rank_lut(ids_desc[:k_eff], k_eff, max_bins)
    ranks = lut[jnp.clip(bin_ids, 0, max_bins - 1)]
    ranks = jnp.where(ranks >= k_eff, marker, ranks)
    idx = jnp.where(bin_ids >= 0, ranks, marker).astype(jnp.int32)

    # --- index alignment (paper Sec. IV-C) -------------------------------
    be = block_elems
    edge = coll.right_edge_exchange(idx[:be], axis,
                                    jnp.full((be,), marker, jnp.int32))
    ext = jnp.concatenate([idx, edge])               # (ln + be,)

    # int32 element offsets: fine for n < 2^31 (8.6 GB f32 per variable);
    # production runs on real multi-host fleets enable jax_enable_x64.
    s = jax.lax.axis_index(axis).astype(jnp.int32)
    my_lo = s * jnp.int32(ln)
    first_blk = (my_lo + be - 1) // be               # ceil
    nbmax = -(-ln // be)                             # blocks I may own

    packed_rows = []
    valids = []
    for j in range(nbmax):                            # static unroll
        gstart = (first_blk + j) * be
        lstart = (gstart - my_lo).astype(jnp.int32)
        in_range = (gstart < my_lo + ln) & (gstart < n_total)
        lstart = jnp.clip(lstart, 0, ln - 1)
        blk = jax.lax.dynamic_slice(ext, (lstart,), (be,))
        words = kops.pack_bits(blk, b_bits=b_bits, use_pallas=use_pallas)
        packed_rows.append(words)
        valids.append(in_range)
    packed = jnp.stack(packed_rows)                  # (nbmax, wpb)
    valid = jnp.stack(valids)                        # (nbmax,)
    return idx[None], packed[None], valid[None]


class ShardedCompressor:
    """Distributed NUMARCK over one mesh axis (or a flattened mesh).

    ``overlap=True`` double-buffers the device/host split across temporal
    steps: the host finalize (exceptions + entropy + blob assembly) of
    step i runs on a background thread while the caller's next
    ``compress_async``/``add_async`` drives the device analyze/encode of
    step i+1.  At most two finalizes are in flight (one executing + one
    queued), inputs are snapshotted before handing them to the background
    thread, and the blobs are byte-identical to ``overlap=False`` -- both
    modes run the exact same shared finalize.

    Inputs are NumPy arrays or ``jax.Array``s.  A ``jax.Array`` whose
    devices are this compressor's mesh, in any sharding (a field born
    sharded on the chips), stays there: one jitted program re-lays it out
    into the flat ``P(axis)`` layout of the encode stages (span
    ``encode.relayout``) and the exception values are gathered on the
    mesh, so no host copy of the field is made.  Anything else (NumPy,
    an array on other devices) is copied through the host, padded and
    uploaded.  Counter ``encode.input_host_bytes`` counts the input bytes
    each step copied through the host.

    ``chain`` picks the temporal reference chain residency: "auto"
    (default) keeps between-step state sharded and device-resident on the
    mesh whenever the dtype allows (f32, or f64 under jax_enable_x64),
    advancing it with the `_advance_shard` stage; "host" restores the
    original host `reconstruct_from_indices` round-trip.  Blobs are
    byte-identical across residencies and overlap modes.
    """

    def __init__(self, mesh: Mesh, axis: str = "data",
                 params: NumarckParams = NumarckParams(),
                 use_pallas: bool = True, overlap: bool = False,
                 chain: str = chainmod.CHAIN_AUTO):
        if chain not in chainmod.RESIDENCIES:
            raise ValueError(f"unknown chain residency {chain!r}")
        self.mesh = mesh
        self.axis = axis
        self.params = params
        self.use_pallas = use_pallas
        self.overlap = overlap
        self.chain = chain
        self.n_shards = mesh.shape[axis]
        self._q = FinalizeQueue(overlap, name="shard-finalize")
        self._chain: Optional[chainmod.ReferenceChain] = None
        self._step = 0
        # jit caches: a temporal series traces each stage once per
        # (shape, B) signature instead of once per step -- without this the
        # per-step shard_map retrace dominates the sharded hot path.
        self._analyze_fns: Dict[Tuple, object] = {}
        self._encode_fns: Dict[Tuple, object] = {}
        self._advance_fns: Dict[Tuple, object] = {}
        self._entropy_fns: Dict[Tuple, object] = {}
        self._relayout_fns: Dict[Tuple, object] = {}

    def _shardings(self):
        return (NamedSharding(self.mesh, P(self.axis)),
                NamedSharding(self.mesh, P()))

    def _on_mesh(self, arr) -> bool:
        """Is ``arr`` a jax.Array held by exactly this mesh's devices?"""
        return (isinstance(arr, jax.Array)
                and arr.sharding.device_set == set(self.mesh.devices.flat))

    def _relayout_fn(self, shape: tuple, dtype, sharding):
        """One jitted program per (shape, dtype, input sharding): the field
        flattened in C order and zero-padded to ``n_shards * ln``, placed
        as ``P(axis)``.  Across chips this is a collective."""
        key = (tuple(shape), np.dtype(dtype), sharding)
        if key not in self._relayout_fns:
            n = int(np.prod(shape))
            total = self.n_shards * -(-n // self.n_shards)
            # Named, so that the program is ``jit__relayout`` in a trace.
            fn = update_wrapper(partial(_relayout, total=total), _relayout)
            self._relayout_fns[key] = jax.jit(
                fn, out_shardings=self._shardings()[0])
        return self._relayout_fns[key]

    def _relayout(self, arr, dtype=None):
        """A mesh-resident field in the encode stages' layout (cast to
        ``dtype`` when given), under span ``encode.relayout``."""
        with telemetry.span("encode.relayout", n=arr.size):
            out = self._relayout_fn(arr.shape, arr.dtype, arr.sharding)(arr)
            if dtype is not None and out.dtype != dtype:
                out = out.astype(dtype)
            if telemetry.enabled():
                jax.block_until_ready(out)
        return out

    def _gather_fn(self, ln: int, size: int):
        """Exception values at replicated global positions (``size`` of
        them, the padded count), gathered where the shards hold them.
        Shared by every compressor on the same mesh, so a padded count
        compiles once per process, whichever variable met it first."""
        key = (self.mesh, self.axis, ln, size)
        if key not in _GATHER_FNS:
            fn = shard_map(
                partial(_gather_shard, ln=ln, axis=self.axis),
                mesh=self.mesh, in_specs=(P(self.axis), P()),
                out_specs=P(), check_vma=False)
            _GATHER_FNS[key] = jax.jit(fn)
        return _GATHER_FNS[key]

    def _analyze_fn(self, ebytes: int, n: int):
        key = (ebytes, n)
        if key not in self._analyze_fns:
            p = self.params
            fn = shard_map(
                partial(_analyze_shard, max_bins=p.max_bins, b_max=p.b_max,
                        elem_bytes=ebytes, n_total=n, axis=self.axis,
                        use_pallas=self.use_pallas,
                        fixed_domain=p.fixed_domain),
                mesh=self.mesh,
                in_specs=(P(self.axis), P(self.axis), P()),
                out_specs=(P(),) * 6, check_vma=False)
            self._analyze_fns[key] = jax.jit(fn)
        return self._analyze_fns[key]

    def _encode_fn(self, bb: int, k_eff: int, be: int, ln: int, n: int):
        key = (bb, k_eff, be, ln, n)
        if key not in self._encode_fns:
            p = self.params
            fn = shard_map(
                partial(_encode_shard, b_bits=bb, k_eff=k_eff,
                        max_bins=p.max_bins, block_elems=be, ln=ln,
                        n_total=n, axis=self.axis,
                        use_pallas=self.use_pallas),
                mesh=self.mesh,
                in_specs=(P(self.axis), P(self.axis), P(), P(), P()),
                out_specs=(P(self.axis),) * 3, check_vma=False)
            self._encode_fns[key] = jax.jit(fn)
        return self._encode_fns[key]

    def _entropy_fn(self, nbmax: int, wpb: int, L: int):
        """Device entropy stage (jit-cached shard_map): every shard rANS-
        codes its own packed blocks, so index blocks never leave the mesh
        before they are entropy-coded -- only the dense emission buffers
        and 4-byte lane states cross to host for blob assembly."""
        key = (nbmax, wpb, L)
        if key not in self._entropy_fns:
            fn = shard_map(
                partial(_entropy_shard, L=L),
                mesh=self.mesh,
                in_specs=(P(self.axis), P(self.axis)),
                out_specs=(P(self.axis),) * 3, check_vma=False)
            self._entropy_fns[key] = jax.jit(fn)
        return self._entropy_fns[key]

    def _entropy_stage(self, packed, valid: np.ndarray, nblocks: int,
                       nbytes: int) -> List[bytes]:
        """Run the device entropy stage over the mesh-resident packed
        blocks and assemble one self-describing blob per (valid) block in
        global order.  Byte-identical to the single-device device stage
        and to the host ``rans.compress`` of the same packed bytes."""
        P_, nbmax, wpb = packed.shape
        rows_dev = packed.reshape(P_ * nbmax, wpb)
        stride = rans.sample_stride(nbytes)
        samples = np.asarray(rans.sample_words(rows_dev, stride))
        rows_idx = np.flatnonzero(valid)
        assert rows_idx.size == nblocks, (rows_idx.size, nblocks)
        freqs, fcs = rans.tables_from_samples(samples[rows_idx])
        L = rans.lanes_for(nbytes)
        # Invalid (out-of-range) rows get a placeholder table; their
        # lanes are encoded and discarded.
        fc_full = np.tile(rans.pack_fc(
            rans.freq_from_counts(np.zeros(256, np.uint64))),
            (P_ * nbmax, 1))
        fc_full[rows_idx] = fcs
        sharded, _ = self._shardings()
        fc_dev = jax.device_put(fc_full.reshape(P_, nbmax, 256), sharded)
        states, vals, masks = self._entropy_fn(nbmax, wpb, L)(packed,
                                                              fc_dev)
        states = np.asarray(states).reshape(P_ * nbmax, L)
        vals = np.asarray(vals).reshape(P_ * nbmax, -1)
        masks = np.asarray(masks).reshape(P_ * nbmax, -1)
        blobs = []
        for g, r in enumerate(rows_idx):
            def raw_bytes(r=r):
                return (np.asarray(rows_dev[r]).astype("<u4")
                        .tobytes()[:nbytes])

            blobs.append(rans.assemble_blob(nbytes, freqs[g], states[r],
                                            vals[r][masks[r]],
                                            raw_bytes=raw_bytes))
        return blobs

    def _advance_fn(self, bb: int):
        """Chain-advance stage: `_decode_shard` dequantize + on-device
        exception patch from `curr` (jit-cached per B; input shapes key
        the jit cache underneath)."""
        key = (bb,)
        if key not in self._advance_fns:
            fn = shard_map(
                partial(_advance_shard, b_bits=bb,
                        use_pallas=self.use_pallas),
                mesh=self.mesh,
                in_specs=(P(self.axis), P(self.axis), P(self.axis), P()),
                out_specs=P(self.axis), check_vma=False)
            self._advance_fns[key] = jax.jit(fn)
        return self._advance_fns[key]

    # -------------------------------------------------------- device stage
    def _device_encode(self, prev, curr,
                       b_bits: Optional[int] = None) -> DeviceEncoded:
        """Phases 1-5 on device; returns the pre-entropy encode result
        (host numpy) that both the finalize stage and the reconstructed-
        reference chain consume.

        `curr` is a host array (padded + device_put here) or a jax.Array
        on this mesh in any sharding (re-laid out on the mesh; its
        exception values are then gathered there too, and it is never
        copied to the host).  `prev` is a host array, such a mesh-resident
        field, or the mesh-resident chain state: an already padded,
        sharded f32 jax.Array of shape (n_shards * ln,), fed straight
        back in."""
        p = self.params
        on_mesh = self._on_mesh(curr)
        if not on_mesh:
            curr = np.asarray(curr)
        n = int(curr.size)
        if n >= (1 << 31):
            raise ValueError("per-variable n >= 2^31 needs jax_enable_x64 "
                             "(see pipeline offset note)")
        P_ = self.n_shards
        ln = -(-n // P_)
        sharded, _ = self._shardings()
        # Pad so every shard holds ln elements; pads are invalid (prev=0).
        if isinstance(prev, jax.Array) and prev.shape == (P_ * ln,):
            prev_dev = prev
        elif self._on_mesh(prev) and prev.size == n:
            prev_dev = self._relayout(prev, jnp.float32)
        elif isinstance(prev, jax.Array):
            raise ValueError(
                f"device-resident chain state {prev.shape} does not "
                f"match this step's padded layout ({P_ * ln},); "
                "reset() the compressor before changing shapes")
        else:
            prev_f = np.asarray(prev, np.float32).reshape(-1)
            prev_dev = _put_sharded(_pad_to(prev_f, P_ * ln, 0.0), sharded)
        if on_mesh:
            # Source precision for the exception values, f32 for the
            # encode stages (the same array when the field is f32).
            curr_src = self._relayout(curr)
            curr_dev = curr_src.astype(jnp.float32)
            telemetry.counter("encode.input_host_bytes", 0)
        else:
            curr_f = np.asarray(curr, np.float32).reshape(-1)
            curr_dev = _put_sharded(_pad_to(curr_f, P_ * ln, 0.0), sharded)
            telemetry.counter("encode.input_host_bytes", curr.nbytes)
        ebytes = np.dtype(curr.dtype).itemsize

        analyze = self._analyze_fn(ebytes, n)
        # The b_auto fetch is a device sync point: the analyze span covers
        # dispatch + the wait, so it reads as real stage time.
        with telemetry.span("encode.analyze", n=n) as sp_an:
            (b_auto, ids_desc, counts_desc, domain_lo, width,
             est_sizes) = analyze(prev_dev, curr_dev,
                                  jnp.float32(p.error_bound))
            # Replicated out specs: every process holds the full value.
            b_auto = int(np.asarray(b_auto))
        bb = int(b_bits if b_bits is not None
                 else (p.b_bits if p.b_bits is not None else b_auto))
        k_eff = min((1 << bb) - 1, p.max_bins)
        be = p.block_elems(bb)
        if be > ln:
            be = max(32, ln // 32 * 32) if ln >= 32 else 32
            if be > ln:
                raise ValueError(
                    f"shard length {ln} smaller than minimum block (32); "
                    "use fewer shards or larger inputs")

        encode = self._encode_fn(bb, k_eff, be, ln, n)
        with telemetry.span("encode.index", b_bits=bb) as sp_idx:
            idx_dev, packed, valid = encode(prev_dev, curr_dev,
                                            ids_desc, domain_lo, width)
            if telemetry.enabled():
                jax.block_until_ready((idx_dev, packed, valid))

        marker = (1 << bb) - 1
        exc_vals = None
        with telemetry.span("encode.exceptions") as sp_exc:
            if on_mesh:
                exc_counts, pos_dev, k = kops.exception_positions(
                    idx_dev.reshape(-1), n, marker, be)
                exc_pos = np.zeros(0, np.int64)
                exc_vals = np.zeros(0, curr.dtype)
                if k:
                    vals_dev = self._gather_fn(ln, pos_dev.size)(curr_src,
                                                                 pos_dev)
                    pos_h, vals_h = jax.device_get((pos_dev, vals_dev))
                    exc_pos = pos_h[:k].astype(np.int64)
                    exc_vals = vals_h[:k]
            else:
                exc_counts, exc_pos = kops.exception_compact(
                    idx_dev.reshape(-1), n, marker, be)
            valid_np = np.asarray(valid).reshape(-1)
        nblocks = -(-n // be)
        nbytes_block = be * bb // 8
        raws = coded = coded_name = None
        sp_pack_s = 0.0
        with telemetry.span("encode.device_entropy") as sp_de:
            if device_entropy_route(p, n, bb):
                # Entropy-code on the mesh; only emission buffers cross to
                # host.  The packed words never leave the devices un-coded.
                coded = self._entropy_stage(packed, valid_np, nblocks,
                                            nbytes_block)
                coded_name = p.codec
        if coded is None:
            with telemetry.span("encode.pack_fetch") as sp_pack:
                packed_h = np.asarray(packed)
                # Valid blocks in global order (shards own contiguous
                # ranges).
                packed_h = packed_h.reshape(-1, packed_h.shape[-1])
                rows = packed_h[valid_np]    # (nblocks, words_per_block)
                assert rows.shape[0] == nblocks, (rows.shape, nblocks)
                raws = [r.astype("<u4").tobytes()[:nbytes_block]
                        for r in rows]
            sp_pack_s = sp_pack.duration

        # Host copy of the index table (blocks until the device work of
        # THIS step is done; the previous step's finalize may still be
        # running behind us).  With device entropy + device exceptions the
        # finalize never reads it, so only a host-resident reference chain
        # still needs the fetch; idx_dev stays on the mesh for the
        # chain-advance stage either way.
        need_host_idx = coded is None or (
            self._chain is not None
            and self._chain.residency == chainmod.CHAIN_HOST)
        with telemetry.span("encode.idx_fetch") as sp_fetch:
            idx = (np.asarray(idx_dev).reshape(-1)[:n] if need_host_idx
                   else None)

        enc = pipe.EncodedIndices(idx=idx, b_bits=bb, block_elems=be,
                                  n=n, packed=raws, entropy_coded=coded,
                                  entropy_codec=coded_name,
                                  exc_positions=exc_pos,
                                  exc_block_counts=exc_counts,
                                  exc_values=exc_vals,
                                  dtype=curr.dtype if on_mesh else None,
                                  shape=tuple(curr.shape) if on_mesh
                                  else None)
        domain_lo = float(np.asarray(domain_lo))
        width = float(np.asarray(width))
        centers = pipe.topk_centers(np.asarray(ids_desc), k_eff,
                                    domain_lo, width)
        centers = pipe.round_centers(centers, curr.dtype)
        meta = {"b_auto": b_auto,
                "est_sizes": np.asarray(est_sizes).tolist(),
                "n_shards": self.n_shards, "pipeline": "sharded"}
        if telemetry.enabled():
            # Same driver-timing keys as the single-device encode_device;
            # finalize_step folds them into the canonical per-step record.
            meta["telemetry"] = {
                "analyze_s": sp_an.duration,
                "encode_s": (sp_idx.duration + sp_exc.duration + sp_pack_s
                             + sp_fetch.duration),
                "device_entropy_s": sp_de.duration,
            }
        return DeviceEncoded(enc=enc, centers=centers, domain_lo=domain_lo,
                             width=width, meta=meta,
                             idx_dev=idx_dev, curr_dev=curr_dev)

    # --------------------------------------------------------- host stage
    def _finalize_input(self, curr, dev: DeviceEncoded):
        """What the finalize of a step reads of its input: nothing when
        the exception values came from the mesh, else a host array (a
        private copy under overlap, so callers may reuse their buffers)."""
        if dev.enc.exc_values is not None:
            return None
        return np.array(curr, copy=True) if self.overlap else np.asarray(curr)

    def compress_async(self, prev, curr, b_bits: Optional[int] = None
                       ) -> "Future[CompressedStep]":
        """Device-encode now; return a future of the finalized step
        (finalize runs on the background thread when overlap=True, with at
        most two in flight).

        `prev` and `curr` are NumPy arrays or jax.Arrays; one on this
        mesh, in any sharding, is encoded where it lies and never copied
        to the host (see ``_device_encode``).  A host `curr` is
        snapshotted before the background finalize reads it (exception
        values), so callers may reuse their buffers; a device array is
        immutable and needs no snapshot.
        """
        dev = self._device_encode(prev, curr, b_bits)
        step_i, self._step = self._step, self._step + 1
        return self._q.submit(pipe.finalize_step,
                              self._finalize_input(curr, dev), dev.enc,
                              dev.centers, dev.domain_lo, dev.width,
                              self.params, dev.meta,
                              label=f"finalize step {step_i}")

    def compress(self, prev, curr,
                 b_bits: Optional[int] = None) -> CompressedStep:
        return self.compress_async(prev, curr, b_bits).result()

    def _make_chain(self, dtype) -> chainmod.ReferenceChain:
        if (chainmod.resolve_residency(self.chain, dtype)
                == chainmod.CHAIN_DEVICE):
            return _ShardedDeviceChain(self)
        return chainmod.HostReferenceChain()

    # ------------------------------------------------- temporal streaming
    def add_async(self, arr) -> "Future[CompressedStep]":
        """Streaming interface over a temporal series (first call stores a
        lossless anchor).  The reference chain advances from the
        pre-entropy encode result before returning, so the next step's
        device work never waits on this step's entropy stage; with the
        default device-resident chain the state also never leaves the
        mesh.

        `arr` is a NumPy array or a jax.Array.  One on this mesh, in any
        sharding, stays there: a delta step is encoded where it lies, and
        only the anchor's lossless host encode fetches it (once).  Any
        other input is copied through the host."""
        step_i, self._step = self._step, self._step + 1
        if not self._on_mesh(arr):
            arr = np.asarray(arr)
        if self._chain is None or self._chain.empty:
            self._chain = self._make_chain(arr.dtype)
            self._chain.seed(arr)
            telemetry.counter("encode.input_host_bytes", arr.nbytes)
            host = (np.asarray(arr) if isinstance(arr, jax.Array)
                    else arr.copy())
            return self._q.submit(pipe.finalize_anchor, host, self.params,
                                  label=f"anchor step {step_i}")
        dev = self._device_encode(self._chain.peek(), arr)
        if self.params.reference == REF_RECONSTRUCTED:
            self._chain.advance(dev, arr)
        else:
            self._chain.replace(arr)
        return self._q.submit(pipe.finalize_step,
                              self._finalize_input(arr, dev), dev.enc,
                              dev.centers, dev.domain_lo, dev.width,
                              self.params, dev.meta,
                              label=f"finalize step {step_i}")

    def add(self, arr: np.ndarray) -> CompressedStep:
        return self.add_async(arr).result()

    def compress_series(self, arrays) -> List[CompressedStep]:
        """Compress a temporal series; double-buffered when overlap=True."""
        self.reset()
        out: List[CompressedStep] = []
        futs: Deque[Future] = deque()
        for a in arrays:
            futs.append(self.add_async(a))
            while len(futs) > 2:
                out.append(futs.popleft().result())
        out.extend(f.result() for f in futs)
        return out

    def flush(self):
        """Block until every in-flight finalize has completed (re-raises
        the first background exception, if any)."""
        self._q.flush()

    def close(self):
        self._q.close()

    def reference_state(self) -> Optional[np.ndarray]:
        """Host copy of the current chain state (None before the anchor);
        the one explicit boundary where the mesh-resident chain crosses
        to host."""
        if self._chain is None or self._chain.empty:
            return None
        return self._chain.to_host()

    def reset(self):
        """Drop the temporal chain state (next add() writes an anchor)."""
        self._chain = None
        self._step = 0


def _entropy_shard(words_l, fc_l, *, L):
    """Per-shard device entropy: rANS-scan the shard's packed blocks
    (kernels.rans.encode_bytes_body) with their per-block fused tables.
    Returns (states, per-block emission buffers, masks); the host only
    compacts each block's contiguous buffer into its blob."""
    st, vals, masks = rans.encode_bytes_body(
        rans.words_to_bytes(words_l[0]), fc_l[0], L)
    return st[None], vals[None], masks[None]


def _decode_shard(idx_l, prev_l, centers, *, b_bits, use_pallas):
    """Per-shard fused dequantize (Pallas one-hot-MXU gather kernel)."""
    out = kops.dequantize(idx_l, prev_l, centers[0], b_bits=b_bits,
                          use_pallas=use_pallas)
    return out[None]


def _rans_decode_shard_packed(dec_l, states_l, stream_l, *, m, L, b_bits,
                              be):
    """Per-shard device entropy decode of v1 (byte-rANS) blocks: the
    forward L-lane scan (kernels.rans.decode_scan_body) fused with the
    word unpack, symmetric to `_entropy_shard`.  Dummy (padding) rows
    decode to garbage that the caller drops; stream-integrity validation
    happens on host over the real rows only."""
    syms, xf, ptrf = rans.decode_scan_body(dec_l[0], None, states_l[0],
                                           stream_l[0], m, L)
    nbytes = be * b_bits // 8
    idx = rans.unpack_words(rans.bytes_to_words(syms[:, :nbytes]),
                            b_bits, be)
    return idx[None], xf[None], ptrf[None]


def _rans_decode_shard_syms(dec_l, states_l, stream_l, *, m, L, n_sym,
                            b_bits, be):
    """Per-shard device entropy decode of v2 (symbol-rANS) blocks with a
    dense alphabet <= 256 (symbol fused into the decode table)."""
    syms, xf, ptrf = rans.decode_scan_body(dec_l[0], None, states_l[0],
                                           stream_l[0], m, L)
    syms = syms[:, :be].astype(jnp.int32)
    marker = jnp.int32((1 << b_bits) - 1)
    idx = jnp.where(syms >= jnp.int32(n_sym - 1), marker, syms)
    return idx[None], xf[None], ptrf[None]


def _rans_decode_shard_syms_wide(dec_l, sym_l, states_l, stream_l, *, m, L,
                                 n_sym, b_bits, be):
    """Wide-alphabet (> 256 symbols) flavor of `_rans_decode_shard_syms`:
    symbols come from a second slot->symbol table gather."""
    syms, xf, ptrf = rans.decode_scan_body(dec_l[0], sym_l[0], states_l[0],
                                           stream_l[0], m, L)
    syms = syms[:, :be].astype(jnp.int32)
    marker = jnp.int32((1 << b_bits) - 1)
    idx = jnp.where(syms >= jnp.int32(n_sym - 1), marker, syms)
    return idx[None], xf[None], ptrf[None]


def _advance_shard(idx_l, prev_l, curr_l, centers, *, b_bits, use_pallas):
    """Temporal chain advance on the mesh: the same dequantize kernel as
    `_decode_shard` composed with the on-device exception patch from the
    current step (one shared body, ``kops.chain_advance_core``), so
    between-step chain state never leaves the devices."""
    return kops.chain_advance_core(idx_l, prev_l, curr_l, centers[0],
                                   b_bits=b_bits, use_pallas=use_pallas)


class _ShardedDeviceChain(chainmod.ReferenceChain):
    """Mesh-resident reference chain: state is the padded, sharded f32
    (or f64 under x64) array the encode stages consume directly, advanced
    by the driver's jit-cached `_advance_shard` stage."""

    residency = chainmod.CHAIN_DEVICE

    def __init__(self, driver: "ShardedCompressor"):
        super().__init__()
        self._d = driver
        self._n = 0
        self._shape: Optional[tuple] = None
        self._dtype = None

    def _pad_put(self, arr: np.ndarray):
        d = self._d
        flat = np.asarray(arr, pipe.reconstruction_dtype(arr.dtype)
                          ).reshape(-1)
        ln = -(-flat.size // d.n_shards)
        sharded, _ = d._shardings()
        return _put_sharded(_pad_to(flat, d.n_shards * ln, 0.0), sharded)

    def _layout(self, arr):
        """``arr`` in the chain's padded, sharded layout and precision: a
        field on the mesh through the driver's relayout, anything else
        through the host."""
        if self._d._on_mesh(arr):
            return self._d._relayout(
                arr, pipe.reconstruction_dtype(arr.dtype))
        return self._pad_put(arr)

    def seed(self, arr) -> None:
        if not self._d._on_mesh(arr):
            arr = np.asarray(arr)
        if not chainmod.device_supports(arr.dtype):
            raise ValueError(
                f"mesh-resident chain cannot hold {arr.dtype} bit-exactly "
                "(float64 needs jax_enable_x64)")
        self._n, self._shape = arr.size, tuple(arr.shape)
        self._dtype = np.dtype(arr.dtype)
        self._state = self._layout(arr)

    def replace(self, arr) -> None:
        self.seed(arr)

    def advance(self, dev: DeviceEncoded, curr) -> None:
        bb = dev.enc.b_bits
        # Exact cast: centers are a f64 view of dtype-rounded values.
        # Host numpy (not a committed local jax.Array): jit replicates it
        # per the P() in_spec, which stays valid under multi-process
        # meshes where a single-device-committed array would not.
        centers = np.asarray(dev.centers).astype(self._state.dtype)[None]
        # dev.curr_dev is the encode stages' f32 copy; a float64 chain
        # (x64) must patch exceptions from the source-precision values.
        curr_dev = (dev.curr_dev if self._state.dtype == jnp.float32
                    else self._layout(curr))
        fn = self._d._advance_fn(bb)
        self._state = fn(dev.idx_dev.reshape(-1), self._state,
                         curr_dev, centers)

    def to_host(self) -> np.ndarray:
        return (np.asarray(self._state)[: self._n]
                .astype(self._dtype).reshape(self._shape))


class ShardedDecompressor:
    """Distributed reconstruction, mirror image of the sharded encode.

    Steps that qualify for the device decode route
    (``core.compress.device_decode_route`` with uniform-format rans
    blocks) entropy-decode **on the mesh**: a jit-cached shard_map stage
    symmetric to `_entropy_shard` runs the forward rANS scan over each
    shard's blocks, feeding the (also jit-cached) fused dequantize stage
    and the on-device exception patch -- blob to reconstruction with one
    final host fetch.  Everything else inflates on host (block-parallel
    over the shared entropy pool) and uploads; both routes and the
    single-device driver are bit-identical.

    Reconstruction preserves the source dtype: float32 runs the f32
    kernel, float64 runs the dtype-preserving gather path under
    jax_enable_x64 and falls back to the (bit-identical) host
    `decompress_step` when x64 is off -- it never silently truncates f64
    data through an f32 kernel."""

    def __init__(self, mesh: Mesh, axis: str = "data",
                 use_pallas: bool = True):
        self.mesh = mesh
        self.axis = axis
        self.use_pallas = use_pallas
        self.n_shards = mesh.shape[axis]
        # jit caches (same discipline as ShardedCompressor): one traced
        # executable per static signature across a temporal series.
        self._dequant_fns: Dict[Tuple, object] = {}
        self._rans_fns: Dict[Tuple, object] = {}

    def _shardings(self):
        return (NamedSharding(self.mesh, P(self.axis)),
                NamedSharding(self.mesh, P()))

    def _dequant_fn(self, bb: int):
        key = (bb,)
        if key not in self._dequant_fns:
            fn = shard_map(
                partial(_decode_shard, b_bits=bb,
                        use_pallas=self.use_pallas),
                mesh=self.mesh,
                in_specs=(P(self.axis), P(self.axis), P()),
                out_specs=P(self.axis), check_vma=False)
            self._dequant_fns[key] = jax.jit(fn)
        return self._dequant_fns[key]

    def _rans_fn(self, kind: str, **static):
        key = (kind, tuple(sorted(static.items())))
        if key not in self._rans_fns:
            body = {"v1": _rans_decode_shard_packed,
                    "v2": _rans_decode_shard_syms,
                    "v2w": _rans_decode_shard_syms_wide}[kind]
            n_in = 4 if kind == "v2w" else 3
            fn = shard_map(partial(body, **static), mesh=self.mesh,
                           in_specs=(P(self.axis),) * n_in,
                           out_specs=(P(self.axis),) * 3, check_vma=False)
            self._rans_fns[key] = jax.jit(fn)
        return self._rans_fns[key]

    def _parse_uniform(self, step: CompressedStep):
        """Parse a device-codec step's rans blobs for the mesh decode
        stage.  Returns (signature, records) when every block shares one
        blob version / lane count / alphabet (uniform rows are what the
        shard_map stage needs); None sends the step down the
        single-device device route instead (still bit-identical)."""
        sig = None
        recs = []
        nbytes = step.block_elems * step.b_bits // 8
        for blob in step.index_blocks:
            v = rans.blob_version(blob)
            if v == 1:
                nb_, L, freq, states, stream = rans._parse_v1(blob)
                if nb_ != nbytes:
                    return None
                k = (1, L, 256)
            elif v == 2:
                ne, bb, L, freq, states, stream = rans._parse_v2(blob)
                if bb != step.b_bits or ne != step.block_elems:
                    return None
                k = (2, L, freq.size)
            else:
                return None
            if sig is None:
                sig = k
            elif k != sig:
                return None
            recs.append({"freq": freq, "states": states, "stream": stream})
        return sig, recs

    def _rans_decode_stage(self, step: CompressedStep, parsed):
        """Mesh-resident entropy decode: blobs -> sharded (P, nbmax, be)
        int32 indices.  Blocks pad to P * nbmax rows with dummy rows
        (reused tables, lane states at STATE_LO, empty streams) whose
        output is garbage past position n and is never read; validation
        covers the real rows, matching ``decode_np`` semantics."""
        (version, L, n_sym), recs = parsed
        P_ = self.n_shards
        be = step.block_elems
        nblocks = len(recs)
        nbmax = -(-nblocks // P_)
        rows = P_ * nbmax
        m = -(-(be * step.b_bits // 8 if version == 1 else be) // L)
        smax = max(1, max(r["stream"].size for r in recs))
        states = np.full((rows, L), rans.STATE_LO, np.uint32)
        stream = np.zeros((rows, smax), np.uint16)
        dec = np.empty((rows, rans.M), np.uint32)
        sym = None
        cache: Dict[bytes, tuple] = {}
        for i, r in enumerate(recs):
            key = r["freq"].tobytes()
            if key not in cache:
                cache[key] = rans._decode_tables(r["freq"])
            d, s2 = cache[key]
            dec[i] = d
            states[i] = r["states"]
            stream[i, :r["stream"].size] = r["stream"]
            if s2 is not None:
                if sym is None:
                    sym = np.empty((rows, rans.M), np.int32)
                sym[i] = s2
        if rows > nblocks:                    # dummy rows: any valid table
            dec[nblocks:] = dec[0]
            if sym is not None:
                sym[nblocks:] = sym[0]
        sharded, _ = self._shardings()
        dec_dev = jax.device_put(dec.reshape(P_, nbmax, rans.M), sharded)
        st_dev = jax.device_put(states.reshape(P_, nbmax, L), sharded)
        sm_dev = jax.device_put(stream.reshape(P_, nbmax, smax), sharded)
        if version == 1:
            fn = self._rans_fn("v1", m=m, L=L, b_bits=step.b_bits, be=be)
            idx, xf, ptrf = fn(dec_dev, st_dev, sm_dev)
        elif sym is None:
            fn = self._rans_fn("v2", m=m, L=L, n_sym=n_sym,
                               b_bits=step.b_bits, be=be)
            idx, xf, ptrf = fn(dec_dev, st_dev, sm_dev)
        else:
            sym_dev = jax.device_put(sym.reshape(P_, nbmax, rans.M),
                                     sharded)
            fn = self._rans_fn("v2w", m=m, L=L, n_sym=n_sym,
                               b_bits=step.b_bits, be=be)
            idx, xf, ptrf = fn(dec_dev, sym_dev, st_dev, sm_dev)
        n_emit = np.array([r["stream"].size for r in recs], np.int64)
        rans._check_decoded(np.asarray(xf).reshape(rows, L)[:nblocks],
                            np.asarray(ptrf).reshape(rows)[:nblocks],
                            n_emit)
        return idx

    def decompress(self, step: CompressedStep,
                   prev: np.ndarray) -> np.ndarray:
        from repro.core import compress as comp
        cdt = pipe.reconstruction_dtype(step.dtype)
        if cdt == np.float64 and not jax.config.jax_enable_x64:
            return decompress_step(step, prev)
        tele = telemetry.enabled()
        n = step.n
        marker = (1 << step.b_bits) - 1
        P_ = self.n_shards
        parsed = None
        if comp.device_decode_route(step):
            parsed = self._parse_uniform(step)
            if parsed is None:
                # Mixed blob formats (e.g. a marker-heavy ragged tail
                # that stored raw): the single-device device route
                # handles heterogeneous groups -- still device-resident
                # and bit-identical, just not mesh-sharded.
                return decompress_step(step, prev)
        with telemetry.span("decode.entropy") as sp_e:
            if parsed is not None:
                # Mesh-resident entropy decode: blocks distribute
                # contiguously over shards, so the flattened output IS
                # the global element order (dummy-row garbage past n).
                idx_dev = self._rans_decode_stage(step, parsed)
                ln = idx_dev.shape[1] * step.block_elems
                idx_dev = idx_dev.reshape(-1)
                if tele:
                    jax.block_until_ready(idx_dev)
            else:
                # host: inflate + unpack (block-parallel over the shared
                # entropy pool), one upload.
                idx = comp._decode_index_host(step)
                ln = -(-n // P_)
                sharded, _ = self._shardings()
                with telemetry.span("decode.upload"):
                    idx_dev = jax.device_put(
                        _pad_to(idx.astype(np.int32), P_ * ln, marker),
                        sharded)
                    if tele:
                        jax.block_until_ready(idx_dev)
        with telemetry.span("decode.dequant") as sp_d:
            sharded, rep = self._shardings()
            prev_p = _pad_to(np.asarray(prev, cdt).reshape(-1), P_ * ln,
                             0.0)
            centers = step.centers.astype(cdt)[None]
            out = self._dequant_fn(step.b_bits)(
                idx_dev, jax.device_put(prev_p, sharded),
                jax.device_put(centers, rep)).reshape(-1)
            if tele:
                jax.block_until_ready(out)
        with telemetry.span("decode.patch") as sp_p:
            # device: scatter the exception table over the marker lanes
            # (the padded tail may also read as marker, but real markers
            # all precede it in stream order, so the table lands exactly
            # on the first n lanes).
            if step.n_incompressible:
                out = dequant.patch_exceptions(
                    out, idx_dev,
                    jnp.asarray(step.incomp_values.astype(cdt)),
                    b_bits=step.b_bits)
            if tele:
                jax.block_until_ready(out)
        with telemetry.span("decode.fetch") as sp_f:
            res = np.asarray(out)[:n].astype(step.dtype
                                             ).reshape(step.shape)
        if tele:
            comp._record_read(step, entropy_s=sp_e.duration,
                              dequant_s=sp_d.duration,
                              patch_s=sp_p.duration, fetch_s=sp_f.duration,
                              device=parsed is not None)
        return res


def _addressable_rows(arr) -> Tuple[int, np.ndarray]:
    """This process's contiguous rows of an axis-0-sharded array: (global
    row start, stacked host copy).  Only addressable shards are fetched,
    so no payload bytes ever cross processes -- a non-addressable fetch
    is structurally impossible here (jax raises on it)."""
    shards = sorted(arr.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    datas = [np.asarray(s.data) for s in shards]
    starts = [s.index[0].start or 0 for s in shards]
    for i in range(len(starts) - 1):
        if starts[i] + datas[i].shape[0] != starts[i + 1]:
            raise ValueError("addressable shards of one process must be "
                             "contiguous on the mesh axis")
    return int(starts[0]), np.concatenate(datas, axis=0)


class MultiProcessCompressor(ShardedCompressor):
    """Multi-process NUMARCK: the shard_map stages run unchanged over the
    global (cross-process) mesh; each process then writes ONLY its own
    blocks (paper Sec. IV-D collective write analogue).

    Differences from the single-process `ShardedCompressor` path:

      * the packed index blocks are fetched per-process from the
        *addressable* shards only -- payload bytes never cross hosts;
      * exceptions are recovered per-rank by unpacking the rank's own
        packed blocks (the device exception compaction would be a global
        fetch) and gathering values from the host-resident input;
      * the entropy stage runs on each host over its own blocks;
      * output is a `StepFragment` per step per rank, published as a
        ``<path>.g<gen>.rank<k>`` NCK shard file plus a rank-0 NCKM
        manifest (`save_series`).

    Blobs are byte-identical to the single-process driver for every
    concrete codec; ``codec="auto"`` may legitimately pick different
    per-block codecs (its lzma budget is a *global* payload bound the
    ranks cannot see) and is therefore only split-identical, not
    byte-identical.  The temporal reference chain must be mesh-resident
    (the host chain would need a global index fetch).
    """

    def __init__(self, mesh: Mesh, axis: str = "data",
                 params: NumarckParams = NumarckParams(),
                 use_pallas: bool = True, overlap: bool = False,
                 chain: str = chainmod.CHAIN_AUTO):
        super().__init__(mesh, axis, params, use_pallas=use_pallas,
                         overlap=overlap, chain=chain)
        if params.symbol_rans:
            raise ValueError("symbol-level rANS blobs come from the device "
                             "entropy stage; the multi-process driver "
                             "entropy-codes per host (set symbol_rans="
                             "False)")
        if chain == chainmod.CHAIN_HOST:
            raise ValueError("multi-process compression needs the mesh-"
                             "resident reference chain (chain='host' "
                             "would gather the index table)")
        import jax as _jax
        self.rank = _jax.process_index()
        self.num_ranks = _jax.process_count()
        pidx = [d.process_index for d in self.mesh.devices.flat]
        mine = [i for i, pi in enumerate(pidx) if pi == self.rank]
        if mine != list(range(mine[0], mine[0] + len(mine))):
            raise ValueError("one process's devices must be contiguous on "
                             "the mesh axis (use launch.global_mesh)")

    def _make_chain(self, dtype) -> chainmod.ReferenceChain:
        if (chainmod.resolve_residency(self.chain, dtype)
                != chainmod.CHAIN_DEVICE):
            raise ValueError(
                f"multi-process compression of {np.dtype(dtype)} needs "
                "the device-resident chain (float64 requires "
                "jax_enable_x64)")
        return _ShardedDeviceChain(self)

    # ------------------------------------------------- local device stage
    def _device_encode_local(self, prev, curr: np.ndarray,
                             b_bits: Optional[int] = None):
        """Phases 1-5 on the global mesh; fetches only this process's
        packed blocks.  Returns (DeviceEncoded for the chain, local
        payload dict for the fragment finalize)."""
        p = self.params
        curr_np = np.asarray(curr)
        curr_f = np.asarray(curr_np, np.float32).reshape(-1)
        n = curr_f.size
        if n >= (1 << 31):
            raise ValueError("per-variable n >= 2^31 needs jax_enable_x64 "
                             "(see pipeline offset note)")
        P_ = self.n_shards
        ln = -(-n // P_)
        sharded, _ = self._shardings()
        if isinstance(prev, jax.Array):
            if prev.shape != (P_ * ln,):
                raise ValueError(
                    f"device-resident chain state {prev.shape} does not "
                    f"match this step's padded layout ({P_ * ln},); "
                    "reset() the compressor before changing shapes")
            prev_dev = prev
        else:
            prev_f = np.asarray(prev, np.float32).reshape(-1)
            prev_dev = _put_sharded(_pad_to(prev_f, P_ * ln, 0.0), sharded)
        curr_dev = _put_sharded(_pad_to(curr_f, P_ * ln, 0.0), sharded)
        ebytes = np.dtype(curr_np.dtype).itemsize

        analyze = self._analyze_fn(ebytes, n)
        with telemetry.span("encode.analyze", n=n) as sp_an:
            (b_auto, ids_desc, counts_desc, domain_lo, width,
             est_sizes) = analyze(prev_dev, curr_dev,
                                  jnp.float32(p.error_bound))
            b_auto = int(np.asarray(b_auto))
        bb = int(b_bits if b_bits is not None
                 else (p.b_bits if p.b_bits is not None else b_auto))
        k_eff = min((1 << bb) - 1, p.max_bins)
        be = p.block_elems(bb)
        if be > ln:
            be = max(32, ln // 32 * 32) if ln >= 32 else 32
            if be > ln:
                raise ValueError(
                    f"shard length {ln} smaller than minimum block (32); "
                    "use fewer shards or larger inputs")

        encode = self._encode_fn(bb, k_eff, be, ln, n)
        with telemetry.span("encode.index", b_bits=bb) as sp_idx:
            idx_dev, packed, valid = encode(prev_dev, curr_dev,
                                            ids_desc, domain_lo, width)
            if telemetry.enabled():
                jax.block_until_ready((idx_dev, packed, valid))

        nblocks = -(-n // be)
        with telemetry.span("encode.pack_fetch") as sp_pack:
            r0, words = _addressable_rows(packed)
            _, valid_rows = _addressable_rows(valid)
            nrows, nbmax = words.shape[0], words.shape[1]
            words = words.reshape(nrows * nbmax, -1)
            local_words = words[np.asarray(valid_rows).reshape(-1)]
        first_blk = lambda s: -(-(s * ln) // be)          # noqa: E731
        block_start = min(first_blk(r0), nblocks)
        block_stop = min(first_blk(r0 + nrows), nblocks)
        if local_words.shape[0] != block_stop - block_start:
            raise AssertionError(
                f"rank {self.rank}: fetched {local_words.shape[0]} valid "
                f"blocks, layout says [{block_start}, {block_stop})")

        domain_lo = float(np.asarray(domain_lo))
        width = float(np.asarray(width))
        centers = pipe.topk_centers(np.asarray(ids_desc), k_eff,
                                    domain_lo, width)
        centers = pipe.round_centers(centers, curr_np.dtype)
        meta = {"b_auto": b_auto,
                "est_sizes": np.asarray(est_sizes).tolist(),
                "n_shards": self.n_shards, "rank": self.rank,
                "num_ranks": self.num_ranks, "pipeline": "multiprocess"}
        if telemetry.enabled():
            meta["telemetry"] = {
                "analyze_s": sp_an.duration,
                "encode_s": sp_idx.duration + sp_pack.duration,
            }
        enc = pipe.EncodedIndices(idx=None, b_bits=bb, block_elems=be, n=n)
        dev = DeviceEncoded(enc=enc, centers=centers, domain_lo=domain_lo,
                            width=width, meta=meta, idx_dev=idx_dev,
                            curr_dev=curr_dev)
        local = {"words": local_words, "block_start": block_start,
                 "nblocks": nblocks}
        return dev, local

    # ------------------------------------------------------ host finalize
    def _fragment_finalize(self, curr: np.ndarray, dev: DeviceEncoded,
                           local: dict) -> StepFragment:
        """Per-rank finalize: exceptions recovered by unpacking this
        rank's own packed blocks, host entropy over the same blocks.
        Block-for-block byte-identical to `core.pipeline.finalize_step`
        on the concatenated fragments (concrete codecs)."""
        p = self.params
        curr = np.asarray(curr)
        bb, be, n = dev.enc.b_bits, dev.enc.block_elems, int(dev.enc.n)
        marker = (1 << bb) - 1
        nbytes_block = be * bb // 8
        words = local["words"]
        g0 = int(local["block_start"])
        meta = dict(dev.meta)
        drv_tele = meta.pop("telemetry", None) or {}
        with telemetry.span("finalize", n=n, b_bits=bb) as sp_fin:
            with telemetry.span("finalize.exceptions") as sp_exc:
                curr_flat = curr.reshape(-1)
                counts = np.zeros(words.shape[0], np.int64)
                vals = []
                for j in range(words.shape[0]):
                    idx_blk = packing.unpack_indices_np(
                        words[j].astype("<u4").view(np.uint8), be, bb)
                    pos = np.flatnonzero(idx_blk == marker) + (g0 + j) * be
                    pos = pos[pos < n]       # final-block marker padding
                    counts[j] = pos.size
                    vals.append(curr_flat[pos])
                values = (np.concatenate(vals) if vals
                          else np.zeros(0, curr.dtype)
                          ).astype(curr.dtype, copy=False)
            block_codecs: Optional[List[str]] = None
            with telemetry.span("finalize.entropy") as sp_ent:
                raws = [w.astype("<u4").tobytes()[:nbytes_block]
                        for w in words]
                if p.codec == entropy.AUTO_CODEC and len(raws) > 1:
                    per = entropy.choose_block_codecs(raws, p.zlib_level)
                    if len(set(per)) > 1:
                        codec = pipe._primary_codec(per)
                        block_codecs = per
                        blks = entropy.compress_blocks_per_codec(
                            raws, per, level=p.zlib_level,
                            parallel=p.parallel_entropy)
                    else:
                        codec = per[0]
                        blks = entropy.compress_blocks(
                            raws, codec=codec, level=p.zlib_level,
                            parallel=p.parallel_entropy)
                else:
                    codec = entropy.resolve_codec(p.codec, raws,
                                                  p.zlib_level)
                    blks = entropy.compress_blocks(
                        raws, codec=codec, level=p.zlib_level,
                        parallel=p.parallel_entropy)
                sp_ent.set(codec=codec, blocks=len(blks))
            centers = dev.centers
            if centers.size > marker:
                centers = centers[:marker]
            bytes_in = len(raws) * nbytes_block
            bytes_out = sum(len(b) for b in blks)
            sp_fin.set(codec=codec, bytes_in=bytes_in, bytes_out=bytes_out)
        info = dict(
            total_data_num=n, shape=list(curr.shape), dtype=str(curr.dtype),
            bin_centers_number=int(centers.size), elements_per_block=be,
            B=bb, error_bound=p.error_bound, strategy=p.strategy,
            reference=p.reference, domain_lo=dev.domain_lo,
            bin_width=dev.width, is_anchor=False,
            n_blocks=int(local["nblocks"]), codec=codec)
        frag = StepFragment(
            is_anchor=False, block_start=g0, info=info, index_blocks=blks,
            centers=centers if self.rank == 0 else None,
            incomp_values=values, incomp_block_counts=counts,
            block_codecs=block_codecs)
        if telemetry.enabled():
            meta["telemetry"] = {
                "analyze_s": float(drv_tele.get("analyze_s", 0.0)),
                "encode_s": float(drv_tele.get("encode_s", 0.0)),
                "exceptions_s": sp_exc.duration,
                "entropy_s": sp_ent.duration,
                "finalize_s": sp_fin.duration,
                "bytes_in": bytes_in, "bytes_out": bytes_out,
                "entropy_ratio": bytes_in / max(bytes_out, 1),
                "codec": codec, "device_entropy": False,
            }
        frag.meta = meta
        return frag

    def _anchor_fragment(self, arr: np.ndarray) -> StepFragment:
        """Lossless anchor, split by block index: rank k owns the global
        anchor blocks [k*nb/R, (k+1)*nb/R) of the same block grid the
        single-process `finalize_anchor` uses, so per-block bytes match
        it exactly (blocks compress independently)."""
        p = self.params
        arr = np.asarray(arr)
        flat = arr.reshape(-1)
        be_a = max(1, p.block_bytes // flat.dtype.itemsize)
        slices = pipe.block_slices(flat.size, be_a)
        nb = len(slices)
        g_lo = self.rank * nb // self.num_ranks
        g_hi = (self.rank + 1) * nb // self.num_ranks
        with telemetry.span("finalize.anchor", n=arr.size) as sp:
            raws = [flat[s:e].tobytes() for s, e in slices[g_lo:g_hi]]
            codec = entropy.resolve_codec(p.codec, raws, p.zlib_level)
            blks = entropy.compress_blocks(raws, codec=codec,
                                           level=p.zlib_level,
                                           parallel=p.parallel_entropy)
            sp.set(codec=codec)
        info = dict(
            total_data_num=arr.size, shape=list(arr.shape),
            dtype=str(arr.dtype), bin_centers_number=0,
            elements_per_block=be_a, B=0, error_bound=p.error_bound,
            strategy=p.strategy, reference=p.reference, domain_lo=0.0,
            bin_width=0.0, is_anchor=True, n_blocks=nb, codec=codec)
        frag = StepFragment(is_anchor=True, block_start=g_lo, info=info,
                            index_blocks=blks)
        if telemetry.enabled():
            bytes_in = sum(len(r) for r in raws)
            bytes_out = sum(len(b) for b in blks)
            frag.meta["telemetry"] = {
                "analyze_s": 0.0, "encode_s": 0.0, "exceptions_s": 0.0,
                "entropy_s": sp.duration, "finalize_s": sp.duration,
                "bytes_in": bytes_in, "bytes_out": bytes_out,
                "entropy_ratio": bytes_in / max(bytes_out, 1),
                "codec": codec, "device_entropy": False,
            }
        return frag

    # ------------------------------------------------- temporal streaming
    def add_fragment_async(self, arr: np.ndarray) -> "Future[StepFragment]":
        """Streaming multi-process interface: like `add_async`, but the
        future resolves to this rank's StepFragment (first call seeds the
        chain and fragments a lossless anchor)."""
        arr = np.asarray(arr)
        step_i, self._step = self._step, self._step + 1
        # Fleet fault-injection sites (no-ops without REPRO_FAULTS): a
        # rank dying mid-encode, or stalling as a straggler, exercises
        # rank 0's quarantine/rollback commit path.
        inject.fire("rank_crash", step=step_i, rank=self.rank)
        inject.fire("straggler", step=step_i, rank=self.rank)
        if self._chain is None or self._chain.empty:
            self._chain = self._make_chain(arr.dtype)
            self._chain.seed(arr)
            return self._q.submit(self._anchor_fragment, arr.copy(),
                                  label=f"anchor fragment {step_i}")
        dev, local = self._device_encode_local(self._chain.peek(), arr)
        if self.params.reference == REF_RECONSTRUCTED:
            self._chain.advance(dev, arr)
        else:
            self._chain.replace(arr)
        curr_s = np.array(arr, copy=True) if self.overlap else arr
        return self._q.submit(self._fragment_finalize, curr_s, dev, local,
                              label=f"fragment step {step_i}")

    def add_fragment(self, arr: np.ndarray) -> StepFragment:
        return self.add_fragment_async(arr).result()

    def compress_series_fragments(self, arrays) -> List[StepFragment]:
        """This rank's fragments of a temporal series (double-buffered
        when overlap=True), device work in lockstep across ranks."""
        self.reset()
        out: List[StepFragment] = []
        futs: Deque[Future] = deque()
        for a in arrays:
            futs.append(self.add_fragment_async(a))
            while len(futs) > 2:
                out.append(futs.popleft().result())
        out.extend(f.result() for f in futs)
        return out

    def save_series(self, path: str, arrays, names=None, *,
                    generation: Optional[int] = None,
                    manifest_timeout: float = 60.0) -> str:
        """Compress a series and publish it multi-process: every rank
        writes its own ``<path>.g<gen>.rank<k>`` shard file (atomic),
        rank 0 waits for the full file set and commits the NCKM
        manifest.  Returns the manifest path on rank 0, this rank's
        shard path elsewhere.  `NCKReader(path)` then reads the logical
        file; a crashed rank leaves the previous manifest loadable."""
        frags = self.compress_series_fragments(arrays)
        names = (list(names) if names is not None
                 else [f"step{i:04d}" for i in range(len(frags))])
        if len(names) != len(frags):
            raise ValueError(f"{len(names)} names for {len(frags)} steps")
        w = ShardNCKWriter(path, self.rank, self.num_ranks,
                           generation=generation)
        for name, frag in zip(names, frags):
            w.add_fragment(name, frag)
        w.write()
        if self.rank == 0:
            return w.commit_manifest(timeout=manifest_timeout)
        return w.rank_path


__all__ = ["ShardedCompressor", "ShardedDecompressor",
           "MultiProcessCompressor"]
