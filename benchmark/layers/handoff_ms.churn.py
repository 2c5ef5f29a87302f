"""Median ``migrate.apply_moves``: one committed plan actuated (bursts
through the sources, bare flips for rows whose source is dead)."""

from benchmark.harness import plugin


def read(run):
    c = plugin(run.bench, "layers", "_churn")
    return c.median_ms(c.in_window(run, "migrate.apply_moves"))
