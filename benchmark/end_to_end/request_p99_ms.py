"""99th percentile of client-side request time over every request of the
window. Open loop: from the time the request was due. A failure, or an
answer later than the client's timeout, lies beyond any percentile."""

import math

import numpy as np


def latencies_ms(run) -> tuple[np.ndarray, float]:
    lat, timeout = [], 0.0
    for g in run.log.values():
        if isinstance(g, dict) and g.get("kind") in ("open_loop", "closed_loop"):
            ms = (g["done"] - g["due"]) * 1e3
            lat.append(np.where(g["ok"], ms, np.inf))
            timeout = max(timeout, g["timeout_s"] * 1e3)
    return (np.concatenate(lat) if lat else np.zeros(0)), timeout


def read(run):
    lat, timeout = latencies_ms(run)
    if lat.shape[0] == 0:
        return None
    v = float(np.sort(lat)[math.ceil(0.99 * lat.shape[0]) - 1])
    return v if math.isfinite(v) else timeout
