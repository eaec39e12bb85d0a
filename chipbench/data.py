"""Temporal fields made on the device from a seed.

A copy of the statistics of ``repro.data.temporal`` (power-law spectral
field, multiplicative change of volatility ``vol``, a ``static_frac`` of
cells that barely move, a ``jump_frac`` of cells that jump), ported from
NumPy on the host to JAX on the device so that a 100 MB step costs
milliseconds instead of seconds.

A step is ``curr = prev * change``: ``change`` is one field of a small
pool of spectral change fields made in set-up, overridden by the static
cells (fixed per field) and by jumps drawn anew for every step.

Given a sharding, every field is made with it as its output sharding, so
that each chip of a mesh holds its own part; a step is elementwise and
stays on its shards.  The spectral filter of anchors and change fields
(set-up only) runs whole on every chip, which then keeps its part: the
values are those of the unsharded generator.
"""
from __future__ import annotations

from functools import partial
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

# Key streams: one per kind of draw, so that adding a draw never shifts
# another's values.
_ANCHOR, _STATIC, _POOL, _STEP = 1, 2, 3, 4


def seed_key(seed: int) -> jax.Array:
    return jax.random.key(int(seed) % (1 << 62))


def stream_key(key: jax.Array, stream: int, index: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, stream), index)


def correlated_field(key, shape: Sequence[int], slope: float,
                     whole=None) -> jax.Array:
    """Unit-variance random field with a power-law spectrum (FFT filter);
    ``whole``, a replicated sharding, makes every chip filter all of it."""
    white = jax.random.normal(key, shape, jnp.float32)
    if whole is not None:
        white = jax.lax.with_sharding_constraint(white, whole)
    f = jnp.fft.rfftn(white)
    k2 = jnp.zeros((), jnp.float32)
    for d, n in enumerate(shape):
        fr = (jnp.fft.rfftfreq(n) if d == len(shape) - 1
              else jnp.fft.fftfreq(n)).astype(jnp.float32)
        dims = [1] * len(shape)
        dims[d] = fr.shape[0]
        k2 = k2 + fr.reshape(dims) ** 2
    k = jnp.sqrt(k2).at[(0,) * len(shape)].set(1.0)
    out = jnp.fft.irfftn(f * k ** slope, s=tuple(shape))
    if whole is not None:
        out = jax.lax.with_sharding_constraint(out, whole)
    return (out - out.mean()) / (out.std() + 1e-9)


def _anchor(key, *, shape, slope, offset, dtype, whole):
    return (correlated_field(key, shape, slope, whole) + offset).astype(dtype)


def _change(key, *, shape, slope, vol, whole):
    return 1.0 + vol * correlated_field(key, shape, slope, whole)


def _step(prev, change, kstatic, kstep, t, *, static_frac, jump_frac):
    kstep = jax.random.fold_in(kstep, t)
    shape = prev.shape
    k_noise, k_jump, k_size = jax.random.split(kstep, 3)
    static = jax.random.uniform(kstatic, shape) < static_frac
    c = jnp.where(static,
                  1.0 + 1e-6 * jax.random.normal(k_noise, shape), change)
    jumps = jax.random.uniform(k_jump, shape) < jump_frac
    c = jnp.where(jumps, 1.0 + jax.random.normal(k_size, shape), c)
    return (prev.astype(jnp.float32) * c).astype(prev.dtype)


class FieldGen:
    """Anchors, change fields and steps of one field shape, on the device.

    ``stats`` holds ``vol``, ``jump_frac``, ``static_frac``, ``offset`` and
    ``slope`` as in ``repro.data.temporal.TemporalFieldSpec``.  ``sharding``
    (None: the default device) places every field it makes.
    """

    def __init__(self, shape: Sequence[int], dtype: str, stats: dict,
                 sharding: Optional[jax.sharding.NamedSharding] = None):
        self.shape = tuple(int(x) for x in shape)
        self.dtype = jnp.dtype(dtype)
        s = stats
        if sharding is None:
            jit, whole = jax.jit, None
        else:
            jit = partial(jax.jit, out_shardings=sharding)
            whole = jax.sharding.NamedSharding(sharding.mesh,
                                               jax.sharding.PartitionSpec())
        self._anchor = jit(partial(
            _anchor, shape=self.shape, slope=s["slope"], offset=s["offset"],
            dtype=self.dtype, whole=whole))
        self._change = jit(partial(
            _change, shape=self.shape, slope=s["slope"], vol=s["vol"],
            whole=whole))
        self._step = jit(partial(
            _step, static_frac=s["static_frac"], jump_frac=s["jump_frac"]))

    def anchor(self, key, field: int) -> jax.Array:
        return self._anchor(stream_key(key, _ANCHOR, field))

    def change(self, key, index: int) -> jax.Array:
        return self._change(stream_key(key, _POOL, index))

    def step(self, prev, change, key, field: int, t: int) -> jax.Array:
        """Field ``field`` at step ``t`` from its step ``t - 1``."""
        return self._step(prev, change, stream_key(key, _STATIC, field),
                          stream_key(key, _STEP, field), jnp.int32(t))

    def series(self, key, field: int, pool, pool_index) -> Iterator:
        """Steps 0..len(pool_index) of one field, one at a time:
        ``pool_index[t - 1]`` names the change field of step ``t``."""
        curr = self.anchor(key, field)
        yield curr
        for t, j in enumerate(pool_index, start=1):
            curr = self.step(curr, pool[j], key, field, t)
            yield curr
