"""Median server-side time of ``MetricAggregator.record`` (the fan-out's
inner call included, since the parent awaits it)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_red").handler_p50_ms(run)
