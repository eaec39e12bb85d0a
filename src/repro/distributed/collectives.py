"""MPI -> jax.lax collective analogues (paper Sec. IV phase mapping).

| paper                          | here                                   |
|--------------------------------|----------------------------------------|
| MPI_Allreduce(MIN/MAX) ratios  | lax.pmin / lax.pmax                    |
| MPI_Allreduce(SUM) histogram   | lax.psum                               |
| MPI_Scan block boundaries      | exclusive_scan (all_gather + masked    |
|                                | cumsum; static shortcut when shards    |
|                                | are even)                              |
| MPI_Send/Recv index alignment  | lax.ppermute fixed-width edge slices   |
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def allreduce_minmax(lo, hi, axis: str):
    return lax.pmin(lo, axis), lax.pmax(hi, axis)


def allreduce_sum(x, axis: str):
    return lax.psum(x, axis)


def exclusive_scan_sum(x, axis: str):
    """MPI_Exscan analogue: sum of `x` over lower-ranked shards.

    Implemented as all_gather + masked sum -- O(P) payload like a gather-
    based scan; P is the mesh axis size so this is tiny metadata traffic.
    """
    idx = lax.axis_index(axis)
    gathered = lax.all_gather(x, axis)          # (P, ...)
    ranks = jnp.arange(gathered.shape[0])
    mask = (ranks < idx).astype(gathered.dtype)
    return jnp.tensordot(mask, gathered, axes=1)


def right_edge_exchange(x_head, axis: str, fill):
    """Every shard receives the *head* slice of its right neighbour.

    The paper's "index alignment": a block straddling a shard boundary is
    completed from the right neighbour's first elements.  The last shard
    receives `fill`.
    """
    n = lax.axis_size(axis)
    perm = [(s, s - 1) for s in range(1, n)]
    recv = lax.ppermute(x_head, axis, perm)
    is_last = lax.axis_index(axis) == n - 1
    return jnp.where(is_last, fill, recv)


__all__ = ["allreduce_minmax", "allreduce_sum", "exclusive_scan_sum",
           "right_edge_exchange"]
