"""Of the slowest 1 % of the window's open-loop requests (a failure slowest
of all), the share that was DUE while the loop was held: inside a record of
``rio_tpu.tracing.hold_log``."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_holds").tail_due_in_hold_share(run)
