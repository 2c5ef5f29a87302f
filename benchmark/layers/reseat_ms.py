"""Median over the window's churn events of flip -> served (no row on one
of the event's leavers, every rejoiner at its share), by the generator's own
stamps: how long objects stay on dead nodes after members go."""

import statistics

from benchmark.harness import plugin


def read(run):
    took = [
        (ev["t_served"] - ev["t_flip"]) * 1e3
        for ev in plugin(run.bench, "layers", "_churn").events(run)
        if ev["t_served"] == ev["t_served"]
    ]
    return statistics.median(took) if took else None
