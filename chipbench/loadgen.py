"""The one traffic generator: it reads a mix's parameters and drives the
system under test through set-up, the measured window and the check.

Modes (the ``mode`` of a traffic file):

``write``  Streaming output of a simulation.  Every field of the
           configuration gets its own compressor; the window goes
           round-robin over the fields, one writer, closed loop: make the
           field's next step on the device, ``add`` it, write the step to
           its own NCK file (fsynced).  A unit is one field-step made
           durable.
``read``   A reader restoring one field's series.  Set-up compresses and
           archives the series with the program; the window opens the
           archive and restores its steps in order through the reader the
           mix names (``ShardedDecompressor`` on a mesh of the cell's
           chips: host inflate, device dequantize and exception patch),
           placing each on the device, in whole passes.  A unit is one
           step resident on the device.

Every seed gets the same sizes and the same amount of work; the seed
chooses the data and the order of the change fields.
"""
from __future__ import annotations

import json
import os
import shutil
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from chipbench import data as datagen
from chipbench import reference as ref
from chipbench.harness import Check, Spans, Unit


def _params(config: dict):
    from repro.core import NumarckParams
    return NumarckParams(**config.get("params", {}))


def make_driver(config: dict):
    """The system under test, as the configuration names it."""
    if config["driver"] != "TemporalCompressor":
        raise ValueError(f"unknown driver {config['driver']!r}")
    from repro.core import TemporalCompressor
    return TemporalCompressor(_params(config))


def make_reader(traffic: dict, devices):
    """The decompressor a read mix names, over a mesh of the cell's chips."""
    if traffic["reader"] != "ShardedDecompressor":
        raise ValueError(f"unknown reader {traffic['reader']!r}")
    from jax.sharding import Mesh
    from repro.distributed.pipeline import ShardedDecompressor
    return ShardedDecompressor(Mesh(np.array(devices), ("data",)), "data")


@dataclass
class _Field:
    name: str
    comp: object = None
    prev: object = None          # original of the last step, on the device
    t: int = 0                   # last step made
    files: Optional[Dict[int, str]] = None


class Load:
    """Shared state of a cell's run."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 workdir: str, spans: Spans):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.devices = list(devices)
        self.workdir = workdir
        self.spans = spans
        self.error_bound = float(_params(config).error_bound)
        self.n = int(np.prod(config["shape"]))
        self.itemsize = int(np.dtype(config["dtype"]).itemsize)
        self.unit_bytes = self.n * self.itemsize
        self.rng = np.random.default_rng(self.seed)
        self.key = datagen.seed_key(self.seed)
        self.gen = datagen.FieldGen(config["shape"], config["dtype"],
                                    config["stats"])
        # The change fields are the same for every seed; the seed orders
        # them.  How well a step compresses depends mostly on its change
        # field, so a pool drawn from the seed would make the seed change
        # the amount of work.
        pool_key = datagen.seed_key(int(traffic["change_pool_seed"]))
        self.pool = [self.gen.change(pool_key, j)
                     for j in range(int(traffic["change_pool"]))]
        self.pool_index: Dict[tuple, int] = {}
        self._block: List[int] = []
        os.makedirs(workdir, exist_ok=True)

    def draw_change(self, f: int, t: int) -> int:
        """The change field of field ``f``'s step ``t``: every block of
        ``len(pool)`` draws uses each field once, in an order drawn from
        the seed."""
        if not self._block:
            self._block = [int(j) for j in
                           self.rng.permutation(len(self.pool))]
        j = self._block.pop()
        self.pool_index[(f, t)] = j
        return j

    def new_block(self) -> None:
        """Start the next draw on a fresh block."""
        self._block = []

    def originals(self, f: int, t_last: int) -> List[np.ndarray]:
        """Steps 0..t_last of field ``f`` made again from the seed."""
        idx = [self.pool_index[(f, t)] for t in range(1, t_last + 1)]
        return [np.asarray(a) for a in self.gen.series(self.key, f,
                                                        self.pool, idx)]


class WriteLoad(Load):
    mode = "write"

    def setup(self) -> None:
        import jax
        self.fields = [_Field(name) for name in self.config["fields"]]
        for f, fd in enumerate(self.fields):
            fd.comp = make_driver(self.config)
            fd.prev = self.gen.anchor(self.key, f)
            fd.files = {}
            fd.comp.add(fd.prev)
        # One recorded delta step of the first field compiles every program
        # a delta step runs (the compressors share them); the check reads
        # its file like any other.
        unit = self._step(0, self.fields[0])
        warm_nonzero(self.n, unit.exceptions)
        self.new_block()
        jax.block_until_ready([fd.prev for fd in self.fields])

    def _step(self, f: int, fd: _Field) -> Unit:
        from repro.core.container import NCKWriter
        from repro.core.partial import TemporalArchive
        t = fd.t + 1
        j = self.draw_change(f, t)
        t0 = time.perf_counter()
        with self.spans.span("bench.gen"):
            # Made before the program sees it, so that its device time is
            # not counted as the driver's.
            curr = self.gen.step(fd.prev, self.pool[j], self.key, f, t)
            curr.block_until_ready()
        with self.spans.span("bench.add"):
            step = fd.comp.add(curr)
        name = TemporalArchive.step_name(fd.name, t)
        path = os.path.join(self.workdir, f"{name}.nck")
        with self.spans.span("bench.write"):
            w = NCKWriter()
            w.add_step(name, step)
            w.write(path)
        t1 = time.perf_counter()
        fd.prev, fd.t = curr, t
        fd.files[t] = path
        return Unit(t0, t1, self.unit_bytes, os.path.getsize(path),
                    step.b_bits, f, t, step.n_incompressible)

    def window(self, seconds: float) -> List[Unit]:
        """Field-steps until ``seconds`` have gone by and the last block of
        change fields is whole, so that every window holds each change
        field equally often."""
        units: List[Unit] = []
        end = time.perf_counter() + seconds
        i = 1                       # field 0 made its first step in set-up
        while not units or units[-1].t1 < end or len(units) % len(self.pool):
            f = i % len(self.fields)
            units.append(self._step(f, self.fields[f]))
            i += 1
        return units

    def free(self) -> None:
        for fd in self.fields:
            fd.comp.close()
            fd.comp = fd.prev = None

    # ------------------------------------------------------------- check
    def check(self, candidate=None, workers: Optional[int] = None
              ) -> List[Check]:
        """Every field-step written: its file, read back and decoded by the
        reference along the file chain, against the reference's own step
        from the same previous reconstruction and the same original.

        ``candidate(f, t, prev_recon, original)`` stands in for reading the
        program's file (the control passes the reference at a lower
        precision); it returns the reconstruction.  ``workers`` threads
        check fields at once (default: half the host's cores)."""
        work = [(f, fd) for f, fd in enumerate(self.fields) if fd.t]
        series = {f: self.originals(f, fd.t) for f, fd in work}
        ulps = int(self.traffic["mismatch_ulps"])

        def one(item):
            f, fd = item
            D = series[f]
            got = D[0]                  # the anchor is stored losslessly
            out = []
            for t in range(1, fd.t + 1):
                try:
                    if candidate is None:
                        name = os.path.basename(fd.files[t])[:-len(".nck")]
                        new = ref.decode(ref.NCKFile(fd.files[t]), name,
                                         got)[0]
                    else:
                        new = candidate(f, t, got, D[t])
                except (ValueError, KeyError, zlib.error) as e:
                    # A file the reference cannot read back holds no
                    # right element; the chain of this field ends here.
                    print(f"field {f} step {t}: unreadable ({e})",
                          flush=True)
                    out.append((1.0, 0.0))
                    break
                want = ref.compress_step(got, D[t], self.error_bound)[0]
                out.append((mismatch(new, want, ulps),
                            bound_ratio(D[t], new, got, self.error_bound)))
                got = new
            return out

        workers = workers or (os.cpu_count() or 2) // 2
        workers = max(1, min(len(work), workers))
        with ThreadPoolExecutor(workers) as ex:
            rows = [r for rs in ex.map(one, work) for r in rs]
        print("per field-step (mismatch_share, error_bound_ratio): "
              + json.dumps(rows), flush=True)
        lim = self.traffic["limits"]
        return [Check("mismatch_share", max(r[0] for r in rows),
                      lim["mismatch_share"],
                      sum(r[0] > lim["mismatch_share"] for r in rows)),
                Check("error_bound_ratio", max(r[1] for r in rows),
                      lim["error_bound_ratio"],
                      sum(r[1] > lim["error_bound_ratio"] for r in rows))]

    def control(self, precision: str = "bfloat16") -> List[Check]:
        """The check with the reference at ``precision`` in the program's
        place, over the field-steps this run's window made, two fields
        at a time: each runs the reference twice, at both precisions."""
        rnd = ref.rounder(precision)
        E = self.error_bound
        return self.check(lambda f, t, prev, curr: ref.compress_step(
            prev, curr, E, rnd=rnd)[0], workers=2)


class ReadLoad(Load):
    mode = "read"

    def setup(self) -> None:
        from repro.core import TemporalCompressor
        from repro.core.partial import TemporalArchive
        f = int(self.traffic["field"])
        self.var = self.config["fields"][f]
        self.n_steps = int(self.config["steps"])
        idx = [self.draw_change(f, t) for t in range(1, self.n_steps)]
        comp = TemporalCompressor(_params(self.config))
        try:
            steps = [comp.add(a) for a in self.gen.series(self.key, f,
                                                           self.pool, idx)]
        finally:
            comp.close()
        self.path = os.path.join(self.workdir, f"{self.var}.nck")
        TemporalArchive.write(self.path, self.var, steps)
        del steps
        self.pool = []
        self.reader = make_reader(self.traffic, self.devices)
        self.kept: Dict[int, object] = {}
        self.seen: Dict[int, int] = {}
        # One whole pass warms every program the window runs: each step
        # has its own exception count, and the patch compiles per count.
        for _ in self._restore():
            pass
        self.seen.clear()
        self.kept.clear()

    def _restore(self):
        """Restore the archive's steps in order, each onto the device."""
        import jax
        from repro.core.compress import decode_anchor
        from repro.core.partial import TemporalArchive
        arch = TemporalArchive(self.path)
        dev = self.devices[0]
        prev = None
        for i in range(self.n_steps):
            with self.spans.span("bench.restore"):
                step = arch.reader.read_step(TemporalArchive.step_name(
                    self.var, i))
                if step.is_anchor:
                    prev = decode_anchor(step).reshape(step.shape)
                else:
                    prev = self.reader.decompress(step, prev)
                out = jax.device_put(prev, dev)
                out.block_until_ready()
            # Keep one restoration of each step, drawn uniformly from the
            # seed over the window (reservoir sampling), for the check.
            self.seen[i] = self.seen.get(i, 0) + 1
            if self.rng.random() * self.seen[i] < 1.0:
                self.kept[i] = out
            yield i, out

    def window(self, seconds: float) -> List[Unit]:
        """Whole passes over the series until ``seconds`` have gone by:
        every pass holds the same anchor and deltas, so where the window
        closes does not change the mix of work."""
        units: List[Unit] = []
        end = time.perf_counter() + seconds
        while not units or units[-1].t1 < end:
            t0 = time.perf_counter()
            for i, _ in self._restore():
                t1 = time.perf_counter()
                units.append(Unit(t0, t1, self.unit_bytes, 0, 0, 0, i))
                t0 = t1
        return units

    def free(self) -> None:
        pass

    def check(self, rnd=ref.F32) -> List[Check]:
        """Every step kept from the window, fetched from the device, against
        the reference's decoding of the archive (bit for bit)."""
        nck = ref.NCKFile(self.path)
        names = [f"{self.var}_it{i:05d}" for i in range(self.n_steps)]
        want, prev = [], None
        for name in names:
            prev = ref.decode(nck, name, prev, ref.F32)[0]
            want.append(prev)
        if rnd is ref.F32:
            got = {i: np.asarray(a) for i, a in self.kept.items()}
        else:
            got, prev = {}, None
            for i, name in enumerate(names):
                prev = ref.decode(nck, name, prev, rnd)[0]
                got[i] = prev
        rows = [mismatch(got[i], want[i], 0) for i in sorted(got)]
        lim = self.traffic["limits"]["restore_mismatch_share"]
        return [Check("restore_mismatch_share", max(rows), lim,
                      sum(r > lim for r in rows))]

    def control(self, precision: str = "bfloat16") -> List[Check]:
        return self.check(ref.rounder(precision))


def mismatch(got: np.ndarray, want: np.ndarray, ulps: int) -> float:
    """Share of elements whose float32 values lie more than ``ulps`` units
    in the last place apart (0: the bits differ)."""
    g = np.asarray(got, np.float32).reshape(-1)
    w = np.asarray(want, np.float32).reshape(-1)
    if g.size != w.size:
        return 1.0
    return float(np.count_nonzero(ulp_distance(g, w) > ulps)) / max(g.size, 1)


def ulp_distance(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Units in the last place between float32 arrays: the distance of
    their bit patterns on the monotone integer line of floats."""
    def line(x):
        i = x.view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    return np.abs(line(a) - line(b))


def warm_nonzero(n: int, k: int) -> None:
    """The program's exception compaction pads ``jnp.nonzero`` to the next
    power of two of a step's exception count, so a step whose count
    crosses one compiles.  Compile the sizes of counts within a factor of
    four of ``k``, the warm step's, before the window."""
    import jax.numpy as jnp
    mask = jnp.zeros(n, bool)
    for m in sorted({min(1 << (c - 1).bit_length(), n)
                     for c in (k // 4, k // 2, k, 2 * k, 4 * k) if c > 0}):
        jnp.nonzero(mask, size=m, fill_value=n)[0].block_until_ready()


def bound_ratio(orig, recon, prev, error_bound: float) -> float:
    """Worst |D - R| / (E |R_prev|): at most 1 for an element stored as a
    bin, 0 for one stored as it is."""
    d = np.asarray(orig, np.float64).reshape(-1)
    r = np.asarray(recon, np.float64).reshape(-1)
    p = np.abs(np.asarray(prev, np.float64).reshape(-1))
    keep = p > 0
    if not keep.any():
        return 0.0
    return float(np.max(np.abs(d[keep] - r[keep])
                        / (error_bound * p[keep])))


LOADS = {"write": WriteLoad, "read": ReadLoad}


def make_load(config: dict, traffic: dict, seed: int, devices,
              workdir: str, spans: Spans) -> Load:
    shutil.rmtree(workdir, ignore_errors=True)
    return LOADS[traffic["mode"]](config, traffic, seed, devices, workdir,
                                  spans)
