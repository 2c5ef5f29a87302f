"""After a committed two-level solve every node's load is within
``2 x cells - 1`` of its exact largest-remainder share (``cells`` = devices x
chunks of the last committed solve: each cell rounds twice, groups to their
share of the cell and nodes to their share of the group, ``chip_smoke.py``'s
bound), and every row is on a node with capacity.

The capacity vector is recovered from the loads (``reference/quotas.py``: a
directory-only member's is 1, a live server's the step of the 1/8 derate
lattice nearest its load over a full member's mean load; a step is 1/8 of a
share, hundreds of rows, against a miss of at most 15), never read from the
program. A run whose solves were not sharded has ``cells`` 1 and is held to
the exact share."""

import numpy as np

from benchmark.reference import quotas


async def audit(run, phase: str) -> None:
    c = run.cluster
    counts, active = run.log[f"counts.{phase}"], run.log[f"active.{phase}"]
    st = c.placement.stats
    cells = max(1, st.devices) * max(1, st.chunks)
    cap = quotas.infer_capacities(counts, active, c.live_idx)[0]
    want = quotas.largest_remainder(cap, int(counts.sum()))
    miss = np.abs(counts - want)
    run.check(f"{phase}.quota_miss_max_seats", int(miss.max()), 2 * cells - 1)
    run.check(f"{phase}.quota_overflow_seats",
              int(np.maximum(miss - (2 * cells - 1), 0).sum()), 0)
    run.check(f"{phase}.rows_on_nodes_without_capacity", int(counts[cap <= 0].sum()), 0)
