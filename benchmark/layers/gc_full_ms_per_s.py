"""Milliseconds per second of window the interpreter spent in full
collections (``gc.gen2`` stages): each stops every thread of the process."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").gc_full_ms_per_s(run)
