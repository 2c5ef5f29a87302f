"""Median ``solve.device`` of the window's mesh solves: the worker thread's
whole part (features, host block to device shards, the cells, the gather,
the fewest-moves pass), off the servers' loop but under the same interpreter
lock."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_mesh").mesh_calls_ms(run, ("solve.device",))
