"""Milliseconds of hold per second of window that no stage of the program
names: all of an ``unnamed`` hold and, of a named one, what its stages leave
uncovered. The hold log's own blind spot, as ``wave_unattributed_ms`` is the
stage log's."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_holds").hold_unnamed_ms_per_s(run)
