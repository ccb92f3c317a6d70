"""Block-size choice shared by the Pallas kernels.

The TPU kernel compiler takes a block whose last two dimensions are each a
multiple of (8, 128) or the whole array dimension.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple


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


class FlashTiles(NamedTuple):
    """(query rows, key rows) per block of each flash-attention kernel;
    each a multiple of 128 that divides the padded sequence."""
    fwd: Tuple[int, int]
    dkv: Tuple[int, int]
    dq: Tuple[int, int]


def flash_tiles(seq: int, head_dim: int) -> FlashTiles:
    """Tiles for causal flash attention over ``seq`` positions (a multiple
    of 128) with heads of ``head_dim``."""
    t = block(512, seq, 128)
    return FlashTiles(fwd=(t, t), dkv=(t, t), dq=(t, t))
