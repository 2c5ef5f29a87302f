"""The longest stretch that began in the window in which the servers' event
loop did not turn, by the loop's own tick (``rio_tpu.tracing.hold_log``)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_holds").hold_max_ms(run)
