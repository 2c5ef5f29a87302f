"""``gc_full_ms_per_s`` in the closed-loop cell (a name of its own: a layer
metric may only move a metric its cell reports)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").gc_full_ms_per_s(run)
