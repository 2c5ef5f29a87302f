"""Largest-remainder shares in float64, and the audit that holds a
directory's per-node loads to them. Plain NumPy; imports nothing of the
program.

The program's flat solves promise that every schedulable node ends on its
integer share of the directory: ``floor(n * cap[j] / sum(cap))`` plus one
unit for the ``n - sum(floors)`` largest remainders. Which of several
nodes with the SAME remainder get the unit is the solver's choice (it
prefers the fuller node), so the audit accepts either for a tie.
"""

import numpy as np

# The derate lattice of JaxObjectPlacement.sync_load: capacity multipliers
# in 1/8 steps, floor 0.1 -> 0.125 after quantization.
LATTICE = np.arange(1, 9, dtype=np.float64) / 8.0

# Two shares closer than this are one remainder class. The program
# computes shares in float32: at a share of 1,024 to 8,192 seats one ulp
# is 1.2e-4 to 4.9e-4, and a share is one division and one product.
TIE_EPS = 2.0 ** -10


def shares(cap: np.ndarray, n: int) -> np.ndarray:
    """Real-valued fair share of ``n`` seats per node, float64."""
    cap = np.maximum(np.asarray(cap, np.float64), 0.0)
    total = cap.sum()
    if total <= 0.0:
        raise ValueError("no schedulable capacity")
    return cap / total * n


def largest_remainder(cap: np.ndarray, n: int) -> np.ndarray:
    """Integer quotas summing to ``n``: floors, then one unit each to the
    largest remainders (ties to the lower index; see :func:`miss`)."""
    t = shares(cap, n)
    q = np.floor(t).astype(np.int64)
    short = n - int(q.sum())
    order = np.argsort(-(t - q), kind="stable")
    q[order[:short]] += 1
    return q


def miss(
    loads: np.ndarray, cap: np.ndarray, tie_eps: float = TIE_EPS, n: int | None = None
) -> int:
    """Seats by which ``loads`` misses the largest-remainder quotas of
    ``cap``; 0 means every node holds its quota.

    A node whose remainder is clearly above the cut must hold the
    ceiling, one clearly below the floor; a node within ``tie_eps`` of the
    cut may hold either. Seats on a zero-capacity node all count. ``n`` is
    the number of seats there should be (by default those there are).
    """
    loads = np.asarray(loads, np.int64)
    n = int(loads.sum()) if n is None else n
    t = shares(cap, n)
    lo = np.floor(t + tie_eps).astype(np.int64)  # 1023.99995 is 1024
    rem = t - lo
    short = n - int(lo.sum())
    if short < 0 or short > lo.shape[0]:
        return int(np.abs(loads - np.rint(t)).sum())
    srt = np.sort(rem)[::-1]
    # The last remainder that draws a unit and the first that does not:
    # a node strictly above the second is in the top ``short`` under any
    # tie-break, a node strictly below the first under none.
    last_in = srt[short - 1] if short > 0 else np.inf
    first_out = srt[short] if short < srt.shape[0] else -np.inf
    must_ceil = rem > first_out + tie_eps
    must_floor = rem < last_in - tie_eps
    want_lo = np.where(must_ceil, lo + 1, lo)
    want_hi = np.where(must_floor, lo, lo + 1)
    want_hi = np.where(np.asarray(cap) > 0, want_hi, 0)
    want_lo = np.where(np.asarray(cap) > 0, want_lo, 0)
    return int(
        (np.maximum(want_lo - loads, 0) + np.maximum(loads - want_hi, 0)).sum()
    )


def infer_capacities(
    loads: np.ndarray, active: np.ndarray, live_idx: np.ndarray
) -> list[np.ndarray]:
    """The capacity vectors a committed flat solve may have been given,
    recovered from the loads it left. Directory-only members never report
    load, so an active one has capacity 1; a live server's is on
    :data:`LATTICE`, the step nearest to its load over a full-capacity
    node's mean load. Where every node is a live server only the ratios
    show, so there is one candidate for each step the fullest may be on.
    """
    loads = np.asarray(loads, np.float64)
    cap = np.asarray(active, np.float64).copy()
    live = [j for j in np.asarray(live_idx).tolist() if cap[j] > 0]
    full = cap > 0
    full[live] = False
    if full.any():
        bases = [loads[full].mean()]
    elif live:
        bases = [loads[live].max() / step for step in LATTICE[::-1]]
    else:
        raise ValueError("no schedulable node")
    out = []
    for base in bases:
        c = cap.copy()
        for j in live:
            c[j] = LATTICE[np.argmin(np.abs(LATTICE - loads[j] / base))]
        out.append(c)
    return out


def infer_capacity(loads, active, live_idx) -> np.ndarray:
    """Of :func:`infer_capacities`, the vector the loads miss least."""
    cands = infer_capacities(loads, active, live_idx)
    return min(cands, key=lambda c: miss(loads, c))
