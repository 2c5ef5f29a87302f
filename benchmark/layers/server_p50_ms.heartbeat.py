"""Median server-side time of the ``Presence`` heartbeat handler."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_red").handler_p50_ms(run)
