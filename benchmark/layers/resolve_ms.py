"""Median over the window's full re-plans of call -> commit, by the
generator's own stamps: how long the directory takes to plan again."""

import statistics

from benchmark.harness import plugin


def read(run):
    took = [
        (r["t_commit"] - r["t_call"]) * 1e3
        for r in plugin(run.bench, "layers", "_mesh").replans(run)
    ]
    return statistics.median(took) if took else None
