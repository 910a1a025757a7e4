"""Operations and bytes of the fold programs, from their shapes.

Dense fold of one round (``StreamingAggregator`` in tree mode) over n
updates of L fp32 elements:

* the first update: ``acc = update * w``: read L, write L;
* each later one: ``acc = acc + update * w``: read 2L, write L;
* finalize: ``acc * (1 / wsum)``: read L, write L.

int8 fold (``dequant_fold``) of one update over a padded accumulator of
Lp elements in Lp / 8192 blocks: read the int8 data, the weighted fp32
scales and the accumulator, write the accumulator.
"""
from __future__ import annotations

from typing import Tuple

F32 = 4


def dense_fold(n_elems: int, n_updates: int) -> Tuple[float, float]:
    """(flops, bytes) of the dense fold programs of one round."""
    flops = n_elems * (1 + 2 * (n_updates - 1) + 1)
    nbytes = F32 * n_elems * (2 + 3 * (n_updates - 1) + 2)
    return float(flops), float(nbytes)


def dequant_fold(padded: int, n_blocks: int) -> Tuple[float, float]:
    """(flops, bytes) of one ``dequant_fold`` kernel call."""
    flops = 2.0 * padded
    nbytes = padded * 1 + n_blocks * F32 + 2 * padded * F32
    return float(flops), float(nbytes)
