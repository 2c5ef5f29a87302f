"""One more full re-plan, at rest after the window, held against the plain
reference (``reference/two_level.py``): what the route computes, and what it
costs.

The window's re-plans cannot be replayed afterwards (the seats they started
from are gone, and reading four million seats inside the window would be the
audit's own load), so the audit makes one more through the generator's own
``replan`` (same call, same programs, same seeded coarse stage) with the
seats before and after in hand. Compared, each with its limit:

* ``coarse_potentials_max_diff``: the coarse stage's group potentials the
  program committed (the mean over the devices of each one's last cell)
  against the reference's for the same cells' rows, from the same seed, in
  blocks. Limit 5e-5 on the chip, between its two readings there (PERF.md
  section 4): the program reads 3.3e-6 to 4.0e-6 over its seeds (it states
  the kernel ``exp(-cost / eps)`` in bfloat16 inside the iteration; what
  the chip's compiler keeps of that rounding was not pursued), the bfloat16
  control 2.5e-4. A wrong capacity
  share, seed, feature or cell boundary moves a potential by what a member
  more or less in a group does, ``eps x ln(8/7)`` = 6.7e-3, and more; and
  so does a COST in bfloat16: the first chip run of PR 34 read 1.04e-2 here
  because a TPU's default rounds both operands of the float32 affinity
  contraction to bfloat16 (cured in the program, which now names the
  precision). A rehearsal is held to 4e-3 only: the CPU backend's bfloat16
  products read 1.5e-4 to 8.8e-4 at every size tried, as much as the
  chip's control, so there the check tells a wrong input and no precision.
* ``rows_off_reference_loads``: rows above the most, or short of the least,
  that the reference's two-level rounding can leave on a node under any
  tie-break (``two_level.load_bounds``: a cell hands a group the floor or
  the ceiling of its share of the cell and a node the floor or the ceiling
  of its share of the group; equal capacities tie, and the program and the
  reference break ties differently, cell by cell). PERF.md section 4 has
  the two readings the limit lies between: the program's, and the program's
  with its solves' arithmetic in bfloat16, which rounds a 4,181.8-row share
  to a multiple of 32.
* ``moves_over_least``: rows whose seat changed, minus the least any plan
  must move to take the directory from the loads before to the loads after.
  Limit 0: where every row costs the same to move, that is the transport
  cost of the program's plan over the reference's.
* ``moved_share``: rows moved over rows held; limit 1%, the route's promise
  where membership did not change.
"""

import asyncio
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import note, plugin
from benchmark.reference import quotas, two_level

POTENTIALS_LIMIT = 5e-5
POTENTIALS_LIMIT_REHEARSAL = 4e-3
OFF_LOADS_LIMIT_SHARE = 1e-4  # of the directory's rows; PERF.md section 4
MOVED_SHARE_LIMIT = 0.01


def _cell_potentials(run, rec, before, cap, cells: int) -> np.ndarray:
    """Mean over the devices of the reference's potentials for each one's
    last cell (the program's ``pmean`` of every shard's last chunk); a
    thread a cell, NumPy's own part of it off the interpreter lock."""
    c, solver = run.cluster, run.config["solver"]
    devices, chunks = rec["devices"], max(1, rec["chunks"])
    cell = len(c.names) // cells
    node_feat = two_level.hashed(c.node_order, solver["features"])

    def one(dev: int) -> np.ndarray:
        a = (dev * chunks + chunks - 1) * cell
        keys = [f"{c.tname}.{name}" for name in c.names[a : a + cell]]
        feat = two_level.row_features(keys, node_feat, before[a : a + cell], solver["move_cost"])
        return two_level.cell_potentials(
            feat, node_feat.T, cap, cells, solver["eps"], solver["iters"], rec["seed"],
            solver["group_size"],
        )

    with ThreadPoolExecutor(max_workers=devices) as pool:
        return np.mean(list(pool.map(one, range(devices))), axis=0)


async def audit(run, phase: str) -> None:
    c = run.cluster
    before = run.log["seats.last"]
    rec = await plugin(run.bench, "traffic", "full_resolve").replan(
        run, "bench.audit.replan", in_window=False
    )
    after = await c.seats()
    active = await c.active_mask()
    loads0, loads1 = c.counts(before), c.counts(after)
    cells = max(1, rec["devices"]) * max(1, rec["chunks"])
    cap = quotas.infer_capacities(loads1, active, c.live_idx)[0]
    moved = int((after != before).sum())
    run.check(f"{phase}.resolve.moves_over_least",
              moved - two_level.least_moves(loads0, loads1), 0)
    run.check(f"{phase}.resolve.moved_share", moved / len(before), MOVED_SHARE_LIMIT)
    lo, hi = two_level.load_bounds(cap, len(before), cells, run.config["solver"]["group_size"])
    run.check(f"{phase}.resolve.rows_off_reference_loads",
              two_level.rows_off_bounds(loads1, lo, hi), int(OFF_LOADS_LIMIT_SHARE * len(before)))
    if rec["coarse_g"] is None:
        # Not a two-level solve (a rehearsal on the flat route; on the chip
        # the generator holds every re-plan to the configuration's mode).
        return
    ref = await asyncio.to_thread(_cell_potentials, run, rec, before, cap, cells)
    live = np.isfinite(ref)
    off = (np.asarray(rec["coarse_g"], np.float64) - ref)[live]
    diff = float(np.abs(off).max())
    note(f"reference potentials for {rec['devices']} cells: max diff {diff:.2e} "
         f"(mean {off.mean():.2e}, deviation {off.std():.2e})")
    run.check(f"{phase}.resolve.coarse_potentials_max_diff", diff,
              POTENTIALS_LIMIT_REHEARSAL if run.rehearsal else POTENTIALS_LIMIT)
