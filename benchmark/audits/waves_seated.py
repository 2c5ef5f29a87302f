"""Every wave's ids are seated, each on an active node, ``lookup_batch``
equal to what ``assign_batch`` returned, and the directory holds the start
plus every wave."""

import numpy as np


async def audit(run, phase: str) -> None:
    active = run.log[f"active.{phase}"]
    for name, g in run.log.items():
        if not (isinstance(g, dict) and g.get("kind") == "waves"):
            continue
        waves = g["waves"]
        run.check(f"{name}.unseated", sum(w["unseated"] for w in waves), 0)
        run.check(f"{name}.lookup_differs_from_assign", sum(w["differ"] for w in waves), 0)
        run.check(
            f"{name}.on_inactive",
            sum(int((~active[w["seats"][w["seats"] >= 0]]).sum()) for w in waves), 0,
        )
        # The counts the harness summed wave by wave against one whole read
        # of the directory: nothing else seated, moved or dropped a row.
        run.check(
            f"{name}.counts_drift",
            int(np.abs(run.log[f"counts.{phase}"] - g["counts"]).sum()), 0,
        )
