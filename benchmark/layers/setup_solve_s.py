"""Seconds of set-up inside ``rebalance`` (``solve.full`` stages that ended
before the window: the first full solve, executable load included)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").setup_stage_s(run, "solve.full")
