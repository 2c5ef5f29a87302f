"""Daemon solves discarded in the window (an epoch race lost) per churn
event: device and loop time spent while an event waits."""

from benchmark.harness import plugin


def read(run):
    n = len(plugin(run.bench, "layers", "_churn").events(run))
    if not n or "daemons1" not in run.log:
        return None
    before, after = run.log["daemons0"], run.log["daemons1"]
    return (after.get("rebalances_discarded", 0) - before.get("rebalances_discarded", 0)) / n
