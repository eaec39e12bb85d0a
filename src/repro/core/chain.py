"""ReferenceChain: one owner for the prev->recon temporal state.

The paper's temporal chain -- "reconstructed data of step i becomes the
reference of step i+1" (Sec. III) -- used to be an ad-hoc ndarray juggled
by every consumer (TemporalCompressor, ShardedCompressor,
CheckpointManager, serve sessions), and it always dropped to NumPy on the
host between steps.  This module makes the chain a first-class object
with two residencies:

  host    -- NumPy state, advanced by ``pipeline.reconstruct_from_indices``
             (the original behavior; also the fallback for dtypes the
             device cannot hold, e.g. float64 without jax_enable_x64).
  device  -- jax.Array state, advanced by the fused
             ``kernels.ops.chain_advance`` (dequantize + on-device
             exception patch), so the hottest loop in the codebase never
             round-trips through the host.

Both residencies are **bit-identical**: reconstruction arithmetic runs in
the source precision on every path (``pipeline.reconstruction_dtype``),
so a series compressed with a device chain emits byte-identical blobs to
the host chain.  ``to_host()`` is the one explicit boundary where state
is copied off the accelerator (durable writes: checkpoints, session
snapshots, user inspection).

The sharded driver subclasses :class:`ReferenceChain` with a mesh-resident
flavor (``distributed.pipeline``); this module holds the single-device
flavors plus the residency policy.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pipe
from repro.kernels import ops as kops

CHAIN_HOST = "host"
CHAIN_DEVICE = "device"
CHAIN_AUTO = "auto"
RESIDENCIES = (CHAIN_HOST, CHAIN_DEVICE, CHAIN_AUTO)


def device_supports(dtype) -> bool:
    """Can a device-resident chain hold `dtype` bit-exactly?

    f32 always; f64 only under jax_enable_x64 (without it jnp would
    silently downcast and the chain would drift from the host chain).
    Narrower floats compute in f32 but must *store* in their own dtype to
    stay bit-identical with the host chain's per-step rounding -- keep
    them on the host where that rounding is explicit.
    """
    dt = np.dtype(dtype)
    if dt == np.float32:
        return True
    if dt == np.float64:
        return bool(jax.config.jax_enable_x64)
    return False


def resolve_residency(requested: str, dtype) -> str:
    """Residency policy: honor an explicit choice, pick for "auto"."""
    if requested not in RESIDENCIES:
        raise ValueError(f"unknown chain residency {requested!r}; "
                         f"expected one of {RESIDENCIES}")
    if requested == CHAIN_HOST:
        return CHAIN_HOST
    supported = device_supports(dtype)
    if requested == CHAIN_DEVICE:
        if not supported:
            raise ValueError(
                f"device-resident chain cannot hold dtype {np.dtype(dtype)} "
                "bit-exactly (float64 needs jax_enable_x64); use "
                "chain='host' or 'auto'")
        return CHAIN_DEVICE
    return CHAIN_DEVICE if supported else CHAIN_HOST


class ReferenceChain:
    """Owns the prev->recon temporal state of one variable.

    Lifecycle: ``seed(arr)`` on the anchor step, then per delta step
    either ``advance(dev, curr)`` (REF_RECONSTRUCTED: R_i from the
    pre-entropy encode result) or ``replace(arr)`` (REF_ORIGINAL).
    ``peek()`` hands the state back to the driver's encode stage in the
    chain's own residency; ``to_host()`` is the explicit host-copy
    boundary.  Chains treat state arrays as immutable, so ``fork()`` is a
    cheap handle copy -- consumers that must stage an advance and commit
    it later (checkpoint durability ordering) fork, advance the fork, and
    swap it in after the write is durable.
    """

    residency: str = "?"

    def __init__(self):
        self._state: Optional[Any] = None

    @property
    def empty(self) -> bool:
        return self._state is None

    def reset(self) -> None:
        self._state = None

    def fork(self) -> "ReferenceChain":
        return copy.copy(self)

    # -- interface ---------------------------------------------------------
    def seed(self, arr) -> None:
        raise NotImplementedError

    def replace(self, arr) -> None:
        raise NotImplementedError

    def advance(self, dev: pipe.DeviceEncoded, curr) -> None:
        raise NotImplementedError

    def peek(self):
        return self._state

    def to_host(self) -> np.ndarray:
        raise NotImplementedError


class HostReferenceChain(ReferenceChain):
    """NumPy-resident chain (the original behavior)."""

    residency = CHAIN_HOST

    def seed(self, arr) -> None:
        # Private copy: callers may reuse/mutate their buffers.
        self._state = np.array(np.asarray(arr), copy=True)

    def replace(self, arr) -> None:
        self.seed(arr)

    def advance(self, dev: pipe.DeviceEncoded, curr) -> None:
        # Exception values the driver gathered on the device spare a host
        # copy of ``curr``; they are the same values.
        exc = dev.enc.exc_values
        self._state = pipe.reconstruct_from_indices(
            self._state, dev.enc, dev.centers, self._state.dtype,
            incomp_values=exc,
            curr=None if exc is not None else np.asarray(curr))

    def to_host(self) -> np.ndarray:
        # A writable *copy*: chains treat state as immutable (fork()
        # relies on it), so the live array must never escape.
        return self._state.copy()


class DeviceReferenceChain(ReferenceChain):
    """jax.Array-resident chain advanced by the fused dequantize kernel.

    ``use_pallas=None`` picks the Pallas lowering on TPU and the (bit-
    identical) gather lowering elsewhere -- interpret-mode Pallas is for
    kernel tests, not for a per-step hot loop on CPU hosts.
    """

    residency = CHAIN_DEVICE

    def __init__(self, use_pallas: Optional[bool] = None):
        super().__init__()
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self._shape: Optional[tuple] = None

    def seed(self, arr) -> None:
        if not device_supports(np.asarray(arr).dtype):
            raise ValueError(
                f"device chain cannot hold {np.asarray(arr).dtype} "
                "bit-exactly (float64 needs jax_enable_x64)")
        # jnp.array, not asarray: on CPU backends asarray can zero-copy
        # alias the caller's buffer, and callers are allowed to reuse
        # their buffers (same contract as the host chain's seed copy).
        self._state = jnp.array(arr)
        self._shape = self._state.shape

    def replace(self, arr) -> None:
        self.seed(arr)

    def advance(self, dev: pipe.DeviceEncoded, curr) -> None:
        idx = (dev.idx_dev if dev.idx_dev is not None
               else jnp.asarray(dev.enc.idx))
        curr_dev = (dev.curr_dev if dev.curr_dev is not None
                    else jnp.array(curr))     # private copy (see seed)
        # Centers are a float64 view of values already rounded to the data
        # dtype, so this cast is exact.
        centers = jnp.asarray(
            np.asarray(dev.centers).astype(self._state.dtype))
        new = kops.chain_advance(idx, self._state.reshape(-1),
                                 curr_dev.reshape(-1), centers,
                                 b_bits=dev.enc.b_bits,
                                 use_pallas=self.use_pallas)
        self._state = new.reshape(self._shape)

    def to_host(self) -> np.ndarray:
        # np.array (not asarray): jax may hand back a read-only zero-copy
        # view on CPU backends; to_host promises a writable private copy.
        return np.array(self._state)


def make_reference_chain(residency: str, dtype,
                         use_pallas: Optional[bool] = None
                         ) -> ReferenceChain:
    """Factory used by the single-device drivers (compressor, checkpoint)."""
    if resolve_residency(residency, dtype) == CHAIN_DEVICE:
        return DeviceReferenceChain(use_pallas=use_pallas)
    return HostReferenceChain()


# -- serve-side session state ----------------------------------------------

def tree_to_host(tree) -> Any:
    """Copy a pytree of (device) arrays to host numpy leaves."""
    return jax.tree_util.tree_map(np.asarray, tree)


class SessionChain:
    """Handle for device-resident session state (a pytree of jax.Arrays).

    The serve-side analogue of a ReferenceChain: decode caches, resume
    token and position stay on device between requests; ``to_host()`` is
    the explicit durable-write boundary (session snapshots to disk).
    """

    def __init__(self, tree: Dict[str, Any]):
        self._tree = tree

    def __getitem__(self, key: str):
        return self._tree[key]

    @property
    def tree(self) -> Dict[str, Any]:
        return self._tree

    def to_host(self) -> Dict[str, Any]:
        return tree_to_host(self._tree)


__all__ = ["ReferenceChain", "HostReferenceChain", "DeviceReferenceChain",
           "SessionChain", "make_reference_chain", "resolve_residency",
           "device_supports", "tree_to_host",
           "CHAIN_HOST", "CHAIN_DEVICE", "CHAIN_AUTO", "RESIDENCIES"]
