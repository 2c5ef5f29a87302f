"""Lattice steps ``sync_load`` applied per second of window, all nodes
(``rio.load.derate_steps``): each re-prices a node for the next solve."""

from benchmark.harness import plugin


def read(run):
    steps = plugin(run.bench, "layers", "_churn").gauge_delta(run, "rio.load.derate_steps")
    return None if steps is None else steps / (run.window[1] - run.window[0])
