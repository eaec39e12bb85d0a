"""Bytes each measured operation must move, counted from its shapes.

The counts are what any implementation of the operation has to read and
write once, so a rewrite of a kernel cannot make them stale: a kernel
that moves more than this shows a lower share, never one above 100 %.
"""
from __future__ import annotations


def analyze_bytes(n: int, itemsize: int) -> int:
    """Change ratios and their histogram: one read of ``prev`` and of
    ``curr`` (the histogram and the bin ids stay on chip or are counted
    by the stages that store them)."""
    return 2 * n * itemsize


def dequant_bytes(n: int, itemsize: int, b_bits: int) -> int:
    """Chain advance ``R = prev * (1 + centers[idx])``: the index at its
    B bits, one read of ``prev`` and one write of ``R``."""
    return -(-n * b_bits // 8) + 2 * n * itemsize


def roofline_pct(nbytes: float, seconds: float, bytes_per_s: float):
    """Share of the memory roofline: the least time for ``nbytes`` at the
    chip's peak bandwidth over the measured device time.  None when no
    device time was measured."""
    if seconds <= 0:
        return None
    return 100.0 * (nbytes / bytes_per_s) / seconds
