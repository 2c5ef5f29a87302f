"""``quotas.py``'s audit, in a rehearsal too: every node on its exact
largest-remainder share.

``quotas.py`` leaves the CPU rehearsal out, because the greedy mode that
``placement_mode: auto`` resolves to there promises every node its ceiling
and no floor. This configuration's rehearsal names the chip's mode
(``sinkhorn``), whose collapsed full solve repairs to the exact shares and
whose delta solves fill integer quotas by construction, so the rehearsal can
hold the control (``--control bfloat16``) to what the chip holds it to.
Capacities are inferred from the loads (``reference/quotas.py``), never read
from the program."""

from benchmark.reference import quotas


async def audit(run, phase: str) -> None:
    counts, active = run.log[f"counts.{phase}"], run.log[f"active.{phase}"]
    cap = quotas.infer_capacity(counts, active, run.cluster.live_idx)
    run.check(f"{phase}.quota_miss_seats_exact", quotas.miss(counts, cap), 0)
