"""Bytes and operations one ``greedy_balanced_assign(cost, mass, cap,
load)`` call needs, from its shapes.

The call is handed a materialized ``rows x cols`` float32 cost block and
reads it once for the column means; everything else is vectors: the mass
and the output (``rows``), the node vectors (``cols``), one sort of
``cols`` scores, two cumulative sums and a binary search per row."""

import math


def cost(rows: int, cols: int) -> dict:
    return {
        "bytes": 4 * (rows * cols + 3 * rows + 8 * cols),
        "flops": rows * cols + 2 * rows + rows * math.ceil(math.log2(cols)) + 16 * cols,
    }
