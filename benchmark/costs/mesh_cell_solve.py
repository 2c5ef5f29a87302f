"""Bytes and operations ONE cell of the mesh x chunk solve needs
(``mesh_cell_solve`` on one device: a two-level solve of ``rows`` rows over
``nodes`` nodes in groups of ``group_size``), from its shapes, whatever
implements it.

Operations, a multiply-add as two. The affinities: every row against every
node over ``feat`` features, once (the coarse stage takes each group's best
member of them, the fine stage reads the row's own group). Coarse: one
exponential a rows x groups entry, ``iters`` iterations of two passes over
that block (a multiply-add an entry each), and the rounding's softmax,
running sum and comparison (4 an entry). Fine: the same over each row's own
``group_size`` members. The two repairs sort the rows by column: ``rows x
log2(rows)`` comparisons each.

Bytes: the rows x groups block can be made again from the ``rows x feat``
features in every pass, so no pass has to read it from memory; what a call
must move is its inputs and outputs: the features in, assignment and group
out (int32), the node features and the vectors of the node axis.
"""

import math


def cost(rows: int, feat: int, nodes: int, group_size: int, iters: int) -> dict:
    groups = nodes // group_size
    per_entry = 1 + 4 * iters + 4
    return {
        "bytes": 4 * (rows * feat + 2 * rows + feat * nodes + 4 * nodes + 2 * groups),
        "flops": (
            2 * rows * nodes * feat
            + per_entry * rows * (groups + group_size)
            + 2 * rows * max(1, math.ceil(math.log2(rows)))
        ),
    }
