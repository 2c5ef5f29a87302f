"""Median of what a delta solve holds the servers' loop for:
``solve.snapshot`` (under the directory's lock) plus ``solve.apply``."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_churn").delta_calls_ms(
        run, ("solve.snapshot", "solve.apply")
    )
