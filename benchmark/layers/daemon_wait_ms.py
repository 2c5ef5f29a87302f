"""Median ``daemon.wait``: a liveness change first seen by a daemon's poll
or kick -> its solve dispatched (poll remainder, debounce, the floor between
solves, a sibling's solve in flight)."""

from benchmark.harness import plugin


def read(run):
    c = plugin(run.bench, "layers", "_churn")
    return c.median_ms(c.in_window(run, "daemon.wait"))
