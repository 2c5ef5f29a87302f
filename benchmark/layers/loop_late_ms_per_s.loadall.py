"""Milliseconds the load monitors' ticks ran late per second of the window
for a reason other than a full collection, all live servers: the sum over
every in-window lag sample of its lag minus what ``gc.gen2`` stages cover of
it, over the window's length. A sum over all ticks: a 99th percentile of 384
samples flips on whether three ticks fall inside the window's three full
collections, and the servers' ticks fall together, so the plain sum does too."""

from benchmark.harness import plugin


def read(run):
    lags = plugin(run.bench, "layers", "_stages").uncollected_lags(run)
    return None if lags is None else sum(lags) / (run.window[1] - run.window[0])
