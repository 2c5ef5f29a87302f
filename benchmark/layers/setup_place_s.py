"""Seconds of set-up inside ``assign_batch`` (``place.assign`` stages that
ended before the window: seating the directory, the warm-up wave)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").setup_stage_s(run, "place.assign")
