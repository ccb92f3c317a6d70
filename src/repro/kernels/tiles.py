"""Block-size choice shared by the Pallas kernels.

The TPU kernel compiler takes a block whose last two dimensions are each a
multiple of (8, 128) or the whole array dimension.
"""

from __future__ import annotations


def block(pref: int, n: int, align: int) -> int:
    """Block for an array dimension of size ``n``: the whole dimension when
    it fits in ``pref``; else the largest multiple of ``align`` up to
    ``pref`` that divides ``n``; else ``pref`` rounded down to ``align``,
    and a ``pl.cdiv(n, block)`` grid then ends in a partial block."""
    if n <= pref:
        return n
    top = max(align, pref - pref % align)
    for b in range(top, 0, -align):
        if n % b == 0:
            return b
    return top
