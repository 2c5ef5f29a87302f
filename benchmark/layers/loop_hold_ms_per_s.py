"""Milliseconds per second of window in which the servers' event loop did not
turn: the union of the program's own hold log (``rio_tpu.tracing.hold_log``:
a 5 ms tick that ran 10 ms late or more) clipped to the window. The first of
the hold readers: it also prints the run's table of holds by cause."""

from benchmark.harness import plugin


def read(run):
    holds = plugin(run.bench, "layers", "_holds")
    holds.note_table(run)
    return holds.hold_ms_per_s(run)
