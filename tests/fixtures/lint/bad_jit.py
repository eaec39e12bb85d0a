"""Fixture: jit-cache-hygiene violations and sanctioned shapes."""
import functools

import jax
from jax import shard_map


@jax.jit
def module_level_ok(x):                       # sanctioned: module decorator
    return x + 1


@functools.partial(jax.jit, static_argnames=("n",))
def module_partial_ok(x, n):                  # sanctioned: partial decorator
    return x * n


_MODULE_FN = jax.jit(lambda x: x)             # sanctioned: module assignment


def _encode_shard(x):
    f = jax.jit(lambda y: y + 1)              # violation: per-call lambda
    return f(x)


def hot_loop(xs):
    out = []
    for x in xs:
        out.append(jax.jit(step)(x))          # violation: per-call jit
    return out


def step(x):
    return x


class Cached:
    def build(self, key, mesh, spec):
        fn = shard_map(step, mesh=mesh, in_specs=spec, out_specs=spec)
        self._fns[key] = jax.jit(fn)          # sanctioned: keyed two-step
        return self._fns[key]

    def build_direct(self, key):
        self._fns[key] = jax.jit(step)        # sanctioned: keyed store
        return self._fns[key]

    def __init__(self):
        self._fns = {}
        self._one = jax.jit(step)             # violation: unkeyed store
