"""Median time from a wave's ``assign_batch`` call to all its seats read
back, over the window's waves (set-up's warm-up waves are left out)."""

import statistics


def read(run):
    ms = [
        (w["t1"] - w["t0"]) * 1e3
        for g in run.log.values()
        if isinstance(g, dict) and g.get("kind") == "waves"
        for w in g["waves"][len(g["waves"]) - g["timed"]:]
    ]
    return statistics.median(ms) if ms else None
