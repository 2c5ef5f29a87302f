"""Median ``solve.device`` of the window's delta solves: the worker
thread's part (ranks or the displaced set, the class refresh on the device,
the fill), off the servers' loop."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_churn").delta_calls_ms(run, ("solve.device",))
