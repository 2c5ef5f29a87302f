"""Median ``solve.features`` of the window's mesh solves: the hashed
identities of every key and the stay-put pull, rebuilt in the worker thread
at every re-plan."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_mesh").mesh_calls_ms(run, ("solve.features",))
