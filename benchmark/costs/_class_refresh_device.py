"""Bytes and operations one ``_class_refresh_device(base, counts,
cap_alive, g_seed)`` call needs, from its shapes.

The ``m x m`` class cost block is a base row broadcast down the rows with
the stay-put discount taken off the diagonal: every pass can make an entry
from two vectors, so the block need never be in memory, and on the v5e it is
not (a count that reads it from memory on every pass put this program at
246% of its roofline, my chip run, PR 27). What a call needs is arithmetic:
``iters`` Sinkhorn iterations of two log-sum-exp passes (rows, then
columns), per entry and pass a subtraction, a scaling, an exponential and an
accumulation; and the vectors: four inputs, two outputs, and both potentials
read and written once a pass."""


def cost(m: int, iters: int) -> dict:
    return {
        "bytes": 4 * m * (6 + 4 * iters),
        "flops": 4 * m * m * 2 * iters + 16 * m,
    }
