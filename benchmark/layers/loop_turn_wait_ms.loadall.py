"""``loop_turn_wait_ms`` in the cell whose request is turns of the one loop
(a name of its own: a per-layer metric may only move a metric its cell
reports). The cell's only hold reader, so it prints the run's table too."""

from benchmark.harness import plugin


def read(run):
    holds = plugin(run.bench, "layers", "_holds")
    holds.note_table(run)
    return holds.turn_wait_ms(run)
