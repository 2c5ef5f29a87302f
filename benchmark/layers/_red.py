"""Median of the app's handler from the servers' merged RED histograms,
over the window (the rows at its end minus the rows at its start).

The program's buckets are log2 over microseconds and hold 1 request in 8:
fit for a median, too coarse to compare two commits within a few percent.
Inside the median's bucket the reader interpolates geometrically."""

import math


def handler_p50_ms(run):
    key = tuple(run.app.HANDLER)
    before, after = run.log.get("red0", {}), run.log.get("red1", {})
    if key not in after:
        return None
    zero = [0] * len(after[key][1])
    buckets = [a - b for a, b in zip(after[key][1], before.get(key, (0, zero))[1])]
    timed = sum(buckets)
    if timed <= 0:
        return None
    cum = 0
    for i, n in enumerate(buckets):
        if n and cum + n >= timed / 2:
            lo = 2.0 ** (i - 1) if i else 0.5  # bucket i holds [2**(i-1), 2**i) us
            frac = (timed / 2 - cum) / n
            return lo * math.pow(2.0, frac) / 1e3
        cum += n
    return None
