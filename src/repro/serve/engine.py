"""Batched serving engine: prefill + streaming decode with KV/SSM caches.

Serves any arch in the zoo.  Requests are padded into a fixed batch; the
engine jits one prefill and one decode executable per (batch, s_max) and
streams tokens.  This is the serve-side end-to-end driver (examples/
serve_lm.py uses it).

Session persistence: `snapshot_cache` / `load_cache` store a decode cache
(KV or SSM state) in an NCK container through the unified compression
pipeline's entropy stage (`core.entropy` codec registry, parallel host
finalize), so a long-lived session's prefix state can be evicted to disk
and resumed later without re-running prefill.

Sessions are held as `core.chain.SessionChain` handles: the decode cache,
resume token and position stay device-resident between requests and only
cross to host through the handle's explicit `.to_host()` at the
durable-write boundary (`save_session`).
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import NumarckParams, make_anchor
from repro.core.chain import SessionChain
from repro.core.compress import decode_anchor, decode_anchor_device
from repro.core.container import NCKReader, NCKWriter
from repro.faults.errors import IntegrityError
from repro.models.model import Model
from repro.obs import telemetry


def _path_part(k) -> str:
    # DictKey -> .key, SequenceKey -> .idx, GetAttrKey -> .name
    for attr in ("key", "idx", "name"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def _tree_keys(tree) -> List:
    flat = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = [_path_part(k) for k in path]
        if any("/" in p for p in parts):
            raise ValueError(
                f"cache key component contains '/': {parts}; rename the "
                "key or restore with load_cache(path, template=...)")
        flat.append(("/".join(parts), leaf))
    return flat


def snapshot_cache(cache: Any, path: str, codec: str = "zlib",
                   level: int = 6) -> Dict[str, int]:
    """Persist a decode-cache pytree losslessly (entropy-coded anchors)."""
    params = NumarckParams(codec=codec, zlib_level=level)
    w = NCKWriter()
    names = {}
    orig = comp = 0
    for i, (key, leaf) in enumerate(sorted(_tree_keys(cache))):
        arr = np.asarray(leaf)
        var = f"c{i:04d}"
        names[var] = key
        st = make_anchor(arr, params)
        orig += arr.nbytes
        comp += st.nbytes
        w.add_step(var, st)
    w.add_array("__names__",
                np.frombuffer(json.dumps(names).encode(), np.uint8))
    w.write(path)
    return {"orig_bytes": orig, "comp_bytes": comp}


def load_cache(path: str, template: Any = None,
               device: bool = False) -> Any:
    """Inverse of snapshot_cache; with `template`, leaves are reshaped and
    cast onto the template pytree (e.g. restoring device placement via a
    jitted identity afterwards).

    ``device=True`` decodes each anchor through the device route
    (`core.compress.decode_anchor_device`): blob bytes entropy-decode on
    the accelerator and the leaf materialises there directly -- no host
    reconstruction + re-upload round trip.  Bit-identical to the host
    path; leaves come back as jax Arrays instead of numpy."""
    r = NCKReader(path)
    names = json.loads(bytes(r.read_array("__names__")).decode())
    dec = decode_anchor_device if device else decode_anchor
    flat = {key: dec(r.read_step(var)) for var, key in names.items()}
    if template is None:
        root: Dict = {}
        for key, arr in flat.items():
            parts = key.split("/")
            d = root
            for p in parts[:-1]:
                d = d.setdefault(p, {})
            d[parts[-1]] = arr
        return root
    keyed = _tree_keys(template)
    treedef = jax.tree_util.tree_structure(template)
    leaves = []
    for key, leaf in keyed:
        arr = flat[key].reshape(np.shape(leaf))
        dtype = getattr(leaf, "dtype", None)
        leaves.append(arr.astype(dtype) if dtype is not None else arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_out: int = 0

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / self.decode_s if self.decode_s else 0.0


class Engine:
    def __init__(self, model: Model, params, batch_size: int, s_max: int,
                 keep_session: bool = False):
        """`keep_session=True` retains each generate()'s final decode state
        (cache + next token + position) on the engine for
        save_session/resume (costs one cache of device memory between
        requests; off by default)."""
        self.model = model
        self.params = params
        self.B = batch_size
        self.s_max = s_max
        self.keep_session = keep_session
        # Engines are long-lived (one per serving process); constructor
        # traces happen once per instance, not per request.
        # repro-lint: disable=jit-cache-hygiene
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, s_max=s_max))
        # repro-lint: disable=jit-cache-hygiene
        self._decode = jax.jit(
            lambda p, c, tok, pos: model.decode(p, c, token=tok, pos=pos))
        self.stats = ServeStats()
        # Device-resident session handle (cache + next token + position);
        # host copies happen only through its .to_host() in save_session.
        self._session: Optional[SessionChain] = None
        # aval-only (shape/dtype) session template, recorded on the first
        # decode loop: lets load_session restore the exact traced avals on
        # any engine that has generated once, even with keep_session=False
        self._sess_template = None

    # Back-compat views of the session handle.
    @property
    def last_cache(self):
        """Decode cache of the last retained generate (device-resident)."""
        return self._session["cache"] if self._session is not None else None

    @property
    def last_tok(self):
        """Next (not yet emitted) token of the retained session."""
        return self._session["tok"] if self._session is not None else None

    @property
    def last_pos(self):
        """Absolute position of last_tok."""
        return self._session["pos"] if self._session is not None else None

    def save_session(self, path: str, codec: str = "zlib") -> Dict[str, int]:
        """Snapshot the last request batch's decode state to disk (cache +
        resume token/position, so the session restarts mid-stream).

        This is the durable-write boundary: the one place the
        device-resident session handle crosses to host (`.to_host()`)."""
        if self._session is None:
            raise RuntimeError(
                "no session cache retained: construct the Engine with "
                "keep_session=True and call generate() first")
        with telemetry.span("serve.save_session", path=path, codec=codec):
            return snapshot_cache(self._session.to_host(), path,
                                  codec=codec)

    def load_session(self, path: str):
        """Reload a snapshotted decode state and place it on device.

        Leaves decode straight onto the device (`load_cache(...,
        device=True)`: blob bytes entropy-decode on the accelerator, no
        host reconstruction + re-upload round trip); re-casting through
        the recorded session template and `jax.device_put` reproduces the
        exact avals the jitted decode executable was traced with, so
        `resume()` streams through the cached executable without a
        retrace (and without a per-step host->device transfer).  Requires
        one prior `generate()` on this engine (any keep_session setting)
        to have recorded the template.
        """
        names = json.loads(bytes(
            NCKReader(path).read_array("__names__")).decode())
        if not any(k == "pos" or k.split("/", 1)[0] == "cache"
                   for k in names.values()):
            raise ValueError(
                f"{path}: not an Engine session file (no cache/tok/pos "
                "record -- bare snapshot_cache() files predate the resume "
                "format; re-save with Engine.save_session)")
        if self._sess_template is None:
            raise RuntimeError(
                "load_session needs the session template: call generate() "
                "once on this engine first (any keep_session setting)")
        with telemetry.span("serve.load_session", path=path):
            try:
                sess = jax.device_put(load_cache(path,
                                                 template=self._sess_template,
                                                 device=True))
            except IntegrityError as e:
                # A flipped bit in a cold session must never resurrect as
                # wrong KV state; surface it with session context so the
                # caller can evict/refetch the snapshot.
                raise IntegrityError(
                    f"session snapshot {path} failed integrity "
                    f"verification and was not restored: {e}") from e
            self._session = SessionChain(sess)
        return self.last_cache

    def _decode_loop(self, cache, tok, pos, max_new: int, greedy: bool,
                     key, keep: bool) -> np.ndarray:
        """Shared streaming loop of generate/resume (same jitted callable)."""
        out = []
        t0 = time.perf_counter()
        with telemetry.span("serve.decode_loop",
                            max_new=max_new, batch=self.B):
            for i in range(max_new):
                out.append(np.asarray(tok)[:, 0])
                logits, cache = self._decode(self.params, cache, tok, pos)
                if greedy or key is None:
                    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
                else:
                    key, sub = jax.random.split(key)
                    tok = jax.random.categorical(sub,
                                                 logits[:, -1])[:, None]
                tok = tok.astype(jnp.int32)
                pos = pos + 1
            jax.block_until_ready(tok)
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.tokens_out += max_new * self.B
        if self._sess_template is None:
            self._sess_template = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                {"cache": cache, "tok": tok, "pos": pos})
        if keep:
            self._session = SessionChain({"cache": cache, "tok": tok,
                                          "pos": pos})
        return np.stack(out, axis=1)

    def generate(self, prompts: np.ndarray, max_new: int = 16,
                 greedy: bool = True, key=None) -> np.ndarray:
        """prompts (B, S0) int32 -> (B, max_new) int32 generated tokens."""
        assert prompts.shape[0] == self.B
        t0 = time.perf_counter()
        with telemetry.span("serve.prefill",
                            batch=self.B, s0=int(prompts.shape[1])):
            logits, cache, pos = self._prefill(
                self.params, {"tokens": jnp.asarray(prompts)})
            jax.block_until_ready(logits)
        self.stats.prefill_s += time.perf_counter() - t0
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        return self._decode_loop(cache, tok, pos, max_new, greedy, key,
                                 keep=self.keep_session)

    def resume(self, max_new: int = 16, greedy: bool = True,
               key=None) -> np.ndarray:
        """Continue a retained or load_session()-restored stream: no
        prefill, same jitted decode executable as generate().  Always
        advances the session state, so consecutive resume() calls stream
        onward (keep_session only governs whether generate() retains its
        cache between requests)."""
        if self._session is None:
            raise RuntimeError(
                "no session to resume: generate() with keep_session=True "
                "or load_session() first")
        return self._decode_loop(self._session["cache"],
                                 self._session["tok"],
                                 self._session["pos"], max_new, greedy, key,
                                 keep=True)


__all__ = ["Engine", "ServeStats", "snapshot_cache", "load_cache"]
