"""The churn, replayed in float64 over the per-node row counts. Plain
NumPy; imports nothing of the program.

An event flips the active set: some members leave, some rejoin. Whatever
plan serves it has to end with every node on its largest-remainder share of
the rows under the new active set (``quotas.py``), so, from the counts just
before the flip:

* the rows on leavers all have to move;
* a node above the most its share allows has to shed the excess, one below
  the least has to receive the shortfall (a rejoiner starts at 0);
* the least number of rows ANY plan moves is the larger of the total excess
  and the total shortfall: a row that leaves a leaver and lands on a
  rejoiner is one move, not two. A plan that moved fewer left rows behind.

Which of several nodes with the same remainder holds the extra row is the
solver's choice (``quotas.miss`` says so too), hence "the most" and "the
least" a share allows.
"""

import numpy as np

from benchmark.reference import quotas


def bounds(cap: np.ndarray, n: int, tie_eps: float = quotas.TIE_EPS) -> tuple:
    """``(least, most)`` rows each node may hold when ``n`` rows are shared
    by largest remainder over ``cap``; equal where the rule leaves no choice."""
    cap = np.asarray(cap, np.float64)
    t = quotas.shares(cap, n)
    lo = np.floor(t + tie_eps).astype(np.int64)
    rem = t - lo
    short = n - int(lo.sum())
    srt = np.sort(rem)[::-1]
    last_in = srt[short - 1] if short > 0 else np.inf
    first_out = srt[short] if short < srt.shape[0] else -np.inf
    least = np.where(rem > first_out + tie_eps, lo + 1, lo)
    most = np.where(rem < last_in - tie_eps, lo, lo + 1)
    return np.where(cap > 0, least, 0), np.where(cap > 0, most, 0)


def event(before: np.ndarray, cap_after: np.ndarray, leavers) -> dict:
    """What one event asks of any plan, from the counts before its flip and
    the capacities after it (0 for an inactive node)."""
    before = np.asarray(before, np.int64)
    n = int(before.sum())
    least, most = bounds(cap_after, n)
    excess = int(np.maximum(before - most, 0).sum())
    shortfall = int(np.maximum(least - before, 0).sum())
    return {
        "rows_on_leavers": int(before[np.asarray(leavers, np.int64)].sum()),
        "quotas": quotas.largest_remainder(cap_after, n),
        "least_moves": max(excess, shortfall),
    }


def replay(counts0: np.ndarray, active0: np.ndarray, events: list, cap=None) -> list:
    """Play ``events`` (each ``{"leavers": [...], "rejoiners": [...]}``) over
    the counts, every plan taken as the ideal one: after an event every node
    holds its quota. ``cap`` is the capacity of an active node (1 for all by
    default). Returns :func:`event`'s record per event, with the counts and
    the active set it ended on."""
    counts = np.asarray(counts0, np.int64).copy()
    active = np.asarray(active0, bool).copy()
    cap = np.ones(counts.shape[0]) if cap is None else np.asarray(cap, np.float64)
    out = []
    for ev in events:
        active[np.asarray(ev["leavers"], np.int64)] = False
        active[np.asarray(ev["rejoiners"], np.int64)] = True
        rec = event(counts, np.where(active, cap, 0.0), ev["leavers"])
        counts = rec["quotas"].copy()
        out.append({**rec, "counts": counts, "active": active.copy()})
    return out


def moved_at_least(before: np.ndarray, after: np.ndarray) -> int:
    """Rows that must have moved between two readings of the counts: what
    the nodes that shrank lost."""
    return int(np.maximum(np.asarray(before, np.int64) - np.asarray(after, np.int64), 0).sum())
