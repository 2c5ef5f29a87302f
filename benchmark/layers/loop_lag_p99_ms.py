"""99th percentile of the load monitors' raw per-tick loop lag in the
window, all live servers (one sample per server per second)."""

import math

from benchmark.harness import plugin


def read(run):
    samples = plugin(run.bench, "layers", "_stages").lag_samples(run)
    if samples is None:
        return None
    lags = sorted(ms for _, ms in samples)
    return float(lags[math.ceil(0.99 * len(lags)) - 1])
