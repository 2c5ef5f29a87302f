"""The longest time in the window a server's event loop ran a due tick late
for a reason other than a full collection: per load-monitor sample, its lag
minus what ``gc.gen2`` stages cover of the interval the loop was late in. A
wave's hold of the loop reads here (some hundreds of ms), and so does a stop
of the whole process by the machine (seconds) unless it falls inside a full
collection, which then reads that long itself."""

from benchmark.harness import plugin


def read(run):
    lags = plugin(run.bench, "layers", "_stages").uncollected_lags(run)
    return None if lags is None else max(lags)
