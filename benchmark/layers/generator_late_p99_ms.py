"""How late the open-loop generator ran: 99th percentile of sent minus due."""

import math

import numpy as np


def read(run):
    late = [
        (g["sent"] - g["due"]) * 1e3 for g in run.log.values()
        if isinstance(g, dict) and g.get("kind") == "open_loop"
    ]
    if not late:
        return None
    late = np.sort(np.concatenate(late))
    return float(late[math.ceil(0.99 * late.shape[0]) - 1])
