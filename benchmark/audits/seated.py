"""Every row asked for is seated, and none sits on an inactive node."""

import numpy as np


async def audit(run, phase: str) -> None:
    c = run.cluster
    ids = [c.names]
    for g in run.log.values():
        if isinstance(g, dict) and g.get("kind") == "waves" and phase != "after_setup":
            ids.append(g["names"])
    ids = np.concatenate(ids)
    seats = await c.seats(ids)
    active = await c.active_mask()
    run.log[f"seats.{phase}"] = run.log["seats.last"] = seats[: len(c.names)]
    run.log[f"counts.{phase}"] = c.counts(seats)
    run.log[f"active.{phase}"] = active
    run.check(f"{phase}.rows_unseated", int((seats < 0).sum()), 0)
    run.check(f"{phase}.rows_beside_asked", abs(c.placement.count() - len(ids)), 0)
    run.check(f"{phase}.rows_on_inactive", int((~active[seats[seats >= 0]]).sum()), 0)
