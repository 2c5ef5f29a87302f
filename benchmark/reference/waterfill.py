"""The capacity-proportional waterfill of ``assign_batch`` in float64.
Plain NumPy; imports nothing of the program.

A batch of ``n_new`` seats is laid over the nodes so that each absorbs its
headroom: the gap between its fair share of the total (seated + incoming)
and what it holds, scaled so that the widths cover the batch exactly. A
node holds the half-integer positions that fall into its interval, so its
increment is the floor or the ceiling of its width whatever the order the
nodes are laid out in.
"""

import numpy as np


def widths(load, cap, n_new: int, dtype=np.float64) -> np.ndarray:
    """Real-valued seats of an ``n_new`` batch per node, carried in ``dtype``."""
    load = np.asarray(load, np.float64).astype(dtype)
    cap = np.maximum(np.asarray(cap, np.float64), 0.0).astype(dtype)
    n_new = dtype(n_new) if dtype is not np.float64 else float(n_new)
    share = cap / cap.sum(dtype=dtype)
    fair = (n_new + load.sum(dtype=dtype)) * share
    head = np.maximum(fair - load, dtype(0))
    if float(head.sum(dtype=dtype)) <= 1e-30:
        head = share * n_new
    return head * (n_new / head.sum(dtype=dtype))


def assign(load, cap, n_new: int, order=None, dtype=np.float64) -> np.ndarray:
    """Node index of each of ``n_new`` unit-mass rows when the nodes are
    laid out in ``order``; every sum, the cumulative ones too, in ``dtype``
    (``ml_dtypes.bfloat16`` is the control of the program's float32)."""
    w = widths(load, cap, n_new, dtype)
    order = np.arange(w.shape[0]) if order is None else np.asarray(order)
    bounds = np.cumsum(w[order], dtype=dtype)
    pos = np.cumsum(np.ones(n_new, dtype), dtype=dtype) - dtype(0.5)
    idx = np.searchsorted(bounds.astype(np.float64), pos.astype(np.float64), side="left")
    return order[np.clip(idx, 0, w.shape[0] - 1)]


def increments(load, cap, n_new: int, order=None, dtype=np.float64) -> np.ndarray:
    """Integer seats per node of an ``n_new`` batch."""
    return np.bincount(assign(load, cap, n_new, order, dtype), minlength=len(load))


def full_member_deviation(before, after, cap, full_idx) -> float:
    """Largest gap, in seats, between what a full-capacity member took of a
    batch and its float64 width. The widths are scaled to the seats the
    full-capacity members took together, so that the live servers' share
    (whose capacity the audit only brackets) drops out."""
    before = np.asarray(before, np.float64)
    inc = np.asarray(after, np.float64) - before
    n_new = int(round(inc.sum()))
    w = widths(before, cap, n_new)[full_idx]
    got = inc[full_idx]
    if w.sum() <= 0:
        return float(np.abs(got).max())
    return float(np.abs(got - w * (got.sum() / w.sum())).max())
