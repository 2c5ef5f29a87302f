"""How long a ready callback waits for its turn of the servers' event loop:
how late the loop's own 5 ms ticks ran, per tick, between the roll-up rows
(``rio_tpu.tracing.tick_log``) that bracket the window. Trains of short
turns show here and in no hold."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_holds").turn_wait_ms(run)
