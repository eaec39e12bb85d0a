"""Plain reference of NUMARCK's semantics, in NumPy on the host.

It imports nothing of the program.  Two parts:

* a reader of the NCK container (header, checksums, the step variables)
  and a decoder of one stored step: zlib blocks, the LSB-first B-bit
  index stream, ``R = prev * (1 + centers[idx])`` and the exception patch;
* a compressor of one step against the previous reconstruction: change
  ratios, the 2E-wide candidate histogram anchored at the smallest ratio
  (or centred on zero when the range does not fit), the top
  ``2^B - 1`` bins by count (ties to the lower bin), B from the file-size
  model of the paper's Eq. 6, and the reconstruction that becomes the
  next step's reference.

``rnd`` rounds every intermediate to the working precision: float32
(the configuration's) for the reference, bfloat16 for the control.

Elementwise work runs in chunks on a pool of threads (NumPy releases the
interpreter's lock inside its loops); every reduction over chunks (a
minimum, a maximum, a sum of counts) is exact, so the result is the same
as one pass over the whole field.
"""
from __future__ import annotations

import json
import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Tuple

import numpy as np

Rounder = Callable[[np.ndarray], np.ndarray]

_MAGICS = {b"NCK1": 1, b"NCK2": 2, b"NCK3": 3, b"NCK4": 4}
_ALIGN = 64


def rounder(precision: str) -> Rounder:
    """Round to ``precision`` and hold the result as float32."""
    if precision == "float32":
        return lambda x: np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"no rounding to {precision!r}")


F32 = rounder("float32")

# Elements per task, and threads: what the tasks hold at once (about 50
# bytes an element) stays under 1 GB whatever the host's core count.
CHUNK = 1 << 20
THREADS = min(16, len(os.sched_getaffinity(0)))
_POOL: List[ThreadPoolExecutor] = []


def pool() -> ThreadPoolExecutor:
    """The threads the reference shares; a task never waits on another."""
    if not _POOL:
        _POOL.append(ThreadPoolExecutor(THREADS,
                                        thread_name_prefix="reference"))
    return _POOL[0]


def chunked(fn: Callable[[slice], object], n: int) -> list:
    """``fn`` of each chunk of ``range(n)``, run on the shared pool."""
    return list(pool().map(fn, [slice(a, min(a + CHUNK, n))
                                for a in range(0, n, CHUNK)]))


# ---------------------------------------------------------------- container

class NCKFile:
    """One NCK data file: its variables, checksums verified on read."""

    def __init__(self, path: str):
        with open(path, "rb") as f:
            raw = f.read()
        magic = raw[:4]
        if magic not in _MAGICS:
            raise ValueError(f"{path}: not an NCK data file ({magic!r})")
        version = _MAGICS[magic]
        (hlen,) = struct.unpack_from("<Q", raw, 4)
        pos = 12
        crc = None
        if version >= 4:
            (crc,) = struct.unpack_from("<I", raw, pos)
            pos += 4
        header = raw[pos:pos + hlen]
        pad = (-(pos + hlen)) % _ALIGN
        if crc is not None and zlib.crc32(raw[pos:pos + hlen + pad]) != crc:
            raise ValueError(f"{path}: header checksum mismatch")
        self.path = path
        self.variables: Dict[str, dict] = json.loads(header)["variables"]
        self._raw = raw
        self._start = pos + hlen + pad

    def bytes(self, name: str) -> bytes:
        v = self.variables[name]
        s = self._start + int(v["offset"])
        data = self._raw[s:s + int(v["nbytes"])]
        if "crc32" in v and zlib.crc32(data) != v["crc32"]:
            raise ValueError(f"{self.path}: variable {name} checksum mismatch")
        return data

    def array(self, name: str) -> np.ndarray:
        v = self.variables[name]
        return np.frombuffer(self.bytes(name), v["dtype"]).reshape(v["shape"])

    def attrs(self, name: str) -> dict:
        return self.variables[name]["attributes"]


def _inflate(blob: bytes, codec: str) -> bytes:
    if codec == "zlib":
        return zlib.decompress(blob)
    if codec == "raw":
        return blob
    raise NotImplementedError(f"the reference decodes zlib and raw, not {codec!r}")


def _blocks(nck: NCKFile, table: str, offsets: str, codec: str) -> bytes:
    data = nck.bytes(table)
    offs = nck.array(offsets)
    return b"".join(pool().map(
        lambda i: _inflate(data[offs[i]:offs[i + 1]], codec),
        range(len(offs) - 1)))


def unpack(stream: bytes, n: int, b_bits: int) -> np.ndarray:
    """First ``n`` values of an LSB-first stream of ``b_bits``-bit fields.

    Eight fields fill ``b_bits`` whole bytes, so the stream is read as rows
    of ``b_bits`` bytes: field ``j`` of a row starts at bit ``j * b_bits``
    and is assembled from the bytes it covers."""
    if len(stream) * 8 < n * b_bits:
        raise ValueError(f"index stream of {len(stream)} bytes holds fewer "
                         f"than {n} fields of {b_bits} bits")
    rows = -(-n // 8)
    buf = np.zeros(rows * b_bits, np.uint8)
    raw = np.frombuffer(stream, np.uint8)[:buf.size]
    buf[:raw.size] = raw
    buf = buf.reshape(rows, b_bits)
    out = np.empty((rows, 8), np.int32)
    mask = np.uint32((1 << b_bits) - 1)

    def part(s):
        for j in range(8):
            first = j * b_bits
            v = np.zeros(s.stop - s.start, np.uint32)
            for k in range(first >> 3, ((first + b_bits - 1) >> 3) + 1):
                v |= buf[s, k].astype(np.uint32) << np.uint32(
                    8 * (k - (first >> 3)))
            out[s, j] = (v >> np.uint32(first & 7)) & mask
    chunked(part, rows)
    return out.reshape(-1)[:n]


def _dequantize(prev: np.ndarray, idx: np.ndarray, lut: np.ndarray,
                rnd: Rounder) -> np.ndarray:
    """``prev * (1 + lut[idx])`` at the working precision, flat."""
    prev = prev.reshape(-1)
    out = np.empty(idx.size, np.float32)

    def part(s):
        out[s] = rnd(rnd(prev[s]) * rnd(1 + lut[idx[s]]))
    chunked(part, idx.size)
    return out


def decode(nck: NCKFile, name: str, prev, rnd: Rounder = F32):
    """Reconstruction of step ``name`` from ``prev``, with its index
    table (None for a losslessly stored anchor) and its B."""
    if f"{name}_anchor_info" in nck.variables:
        info = nck.attrs(f"{name}_anchor_info")
        raw = _blocks(nck, f"{name}_anchor", f"{name}_anchor_offset",
                      info["codec"])
        out = np.frombuffer(raw, info["dtype"]).reshape(info["shape"])
        return rnd(out), None, 0
    info = nck.attrs(f"{name}_info")
    n, b_bits = int(info["total_data_num"]), int(info["B"])
    marker = (1 << b_bits) - 1
    if info.get("block_codecs"):
        raise NotImplementedError("per-block codecs")
    stream = _blocks(nck, f"{name}_index_table",
                     f"{name}_index_table_offset", info["codec"])
    idx = unpack(stream, n, b_bits)
    centers = rnd(nck.array(f"{name}_bin_centers").astype(np.float32))
    lut = np.zeros(marker + 1, np.float32)
    lut[:centers.size] = centers
    out = _dequantize(np.asarray(prev), idx, lut, rnd)
    exc = idx == marker
    values = nck.array(f"{name}_incompressible_table")
    if values.size != int(exc.sum()):
        raise ValueError(f"{nck.path}: {values.size} exception values for "
                         f"{int(exc.sum())} markers")
    out[exc] = rnd(values)
    return out.reshape(info["shape"]), idx, b_bits


# --------------------------------------------------------------- compressor

def file_sizes(counts_desc: np.ndarray, n: int, elem_bytes: int,
               b_max: int) -> np.ndarray:
    """Eq. 6: centers + index + exceptions, in bytes, for B = 1..b_max."""
    cum = np.cumsum(counts_desc.astype(np.float64))
    bs = np.arange(1, b_max + 1)
    ks = np.minimum(2 ** bs - 1, counts_desc.size)
    covered = np.where(ks > 0, cum[np.clip(ks - 1, 0, None)], 0.0)
    exceptions = np.maximum(n - covered, 0.0)
    return (2.0 ** bs) * elem_bytes + n * bs / 8.0 + exceptions * elem_bytes


def encode(prev, curr, error_bound: float, max_bins: int = 1 << 16,
           b_max: int = 16, rnd: Rounder = F32
           ) -> Tuple[int, np.ndarray, np.ndarray]:
    """(B, centers, index table) of ``curr`` against ``prev``."""
    prev = np.asarray(prev).reshape(-1)
    curr = np.asarray(curr).reshape(-1)
    n = curr.size
    r = np.empty(n, np.float32)
    valid = np.empty(n, bool)

    def ratios(s):
        p, c = rnd(prev[s]), rnd(curr[s])
        nonzero = p != 0
        safe = np.where(nonzero, p, np.float32(1))
        with np.errstate(all="ignore"):
            rs = rnd(rnd(c - safe) / safe)
        v = nonzero & np.isfinite(rs) & np.isfinite(c)
        r[s] = np.where(v, rs, np.float32(0))
        valid[s] = v
        return (rs[v].min(), rs[v].max()) if v.any() else None
    ranges = [x for x in chunked(ratios, n) if x is not None]
    if ranges:
        lo, hi = min(x[0] for x in ranges), max(x[1] for x in ranges)
    else:
        lo = hi = np.float32(0)
    width = rnd(np.float32(2.0 * error_bound))
    coverage = rnd(width * np.float32(max_bins))
    domain_lo = lo if rnd(hi - lo) <= coverage else rnd(-0.5 * coverage)
    ids = np.empty(n, np.int32)         # candidate bin, -1 outside

    def bins(s):
        with np.errstate(all="ignore"):
            raw = np.floor(rnd(rnd(r[s] - domain_lo) / width))
        ok = valid[s] & (raw >= 0) & (raw < max_bins)
        ids[s] = np.where(ok, raw, -1)
        return np.bincount(ids[s][ok], minlength=max_bins)
    counts = np.sum(chunked(bins, n), axis=0)
    order = np.argsort(-counts, kind="stable")
    b_bits = int(np.argmin(file_sizes(counts[order], n, r.itemsize,
                                      b_max))) + 1
    k = min((1 << b_bits) - 1, max_bins)
    marker = (1 << b_bits) - 1
    sel = order[:k]
    centers = rnd(np.float64(domain_lo)
                  + (sel.astype(np.float64) + 0.5) * np.float64(width))
    lut = np.full(max_bins, marker, np.int32)
    lut[sel] = np.arange(k)
    idx = np.empty(n, np.int32)

    def index(s):
        i = ids[s]
        idx[s] = np.where(i >= 0, lut[np.clip(i, 0, max_bins - 1)], marker)
    chunked(index, n)
    return b_bits, centers, idx


def reconstruct(prev, curr, b_bits: int, centers: np.ndarray,
                idx: np.ndarray, rnd: Rounder = F32) -> np.ndarray:
    """``R = prev * (1 + centers[idx])``, exceptions stored exactly."""
    marker = (1 << b_bits) - 1
    lut = np.zeros(marker + 1, np.float32)
    lut[:centers.size] = centers
    out = _dequantize(np.asarray(prev), idx, lut, rnd)
    exc = idx == marker
    out[exc] = rnd(np.asarray(curr).reshape(-1)[exc])
    return out.reshape(np.shape(curr))


def compress_step(prev, curr, error_bound: float, rnd: Rounder = F32):
    """One step of the reference chain: (reconstruction, index, B)."""
    b_bits, centers, idx = encode(prev, curr, error_bound, rnd=rnd)
    return reconstruct(prev, curr, b_bits, centers, idx, rnd), idx, b_bits
