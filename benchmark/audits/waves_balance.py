"""What ``assign_batch``'s waterfill does promise, per wave, from the seat
counts read before and after it.

``greedy_balanced_assign`` gives node j the width ``headroom_j * n /
sum(headroom)`` and seats on it the half-integer positions inside its
interval of the cumulative widths, so in exact arithmetic a node takes the
floor or the ceiling of its width: under 1 seat from it. The program
carries the cumulative sum in float32: at 65,536 seats one ulp is 2**-8,
and 1,024 additions move a boundary by well under a quarter seat, which
moves one more half-integer at most; a derate that steps between two waves
shifts every width by the same small factor. The limits are set from chip
readings (PERF.md): about three times the largest a sound run gave (1.04
seats; a spread of 2), a hundred times under the smallest the program's own
kernel gave with its cumulative sums in bfloat16 (309 seats; a spread of 399).
"""

import numpy as np

from benchmark.reference import quotas, waterfill

DEVIATION_LIMIT_SEATS = 3.0
SPREAD_LIMIT_SEATS = 6


def live_outside_bracket(before, after, active, live_idx, n_new: int, slack: int = 2) -> int:
    """Live servers whose share of a batch no combination of derates explains.

    The waterfill gives node j ``n_new * h_j / sum(h)`` seats, ``h`` being
    the headroom under the fair share ``total * cap_j / sum(cap)``. Each live
    server's capacity is on [0.125, 1] and nothing says where, so the sum of
    capacities lies between the two sums below, and with it every fair
    share. ``x / (H + x)`` grows with x and shrinks with H: a server takes
    no more than with its own headroom at its largest and the full-capacity
    members' at their smallest, and no less than the other way round with
    every other live server's at its largest. ``slack`` seats of rounding."""
    before = np.asarray(before, np.float64)
    inc = np.asarray(after, np.float64) - before
    live = np.asarray(live_idx)
    full = np.array(active, bool)
    full[live] = False
    total = before.sum() + n_new
    fair_lo = total / (full.sum() + 1.0 * live.shape[0])  # of a capacity of 1
    fair_hi = total / (full.sum() + 0.125 * live.shape[0])
    full_lo = np.maximum(fair_lo - before[full], 0.0).sum()
    full_hi = np.maximum(fair_hi - before[full], 0.0).sum()
    h_lo = np.maximum(0.125 * fair_lo - before[live], 0.0)
    h_hi = np.maximum(fair_hi - before[live], 0.0)
    hi = n_new * h_hi / np.maximum(full_lo + h_hi, 1e-30)
    lo = n_new * h_lo / np.maximum(full_hi + h_hi.sum() - h_hi + h_lo, 1e-30)
    got = inc[live]
    return int(((got < np.floor(lo) - slack) | (got > np.ceil(hi) + slack)).sum())


async def audit(run, phase: str) -> None:
    c = run.cluster
    active = run.log[f"active.{phase}"]
    full = c.full_idx(active)
    if full.shape[0] == 0:
        return
    for name, g in run.log.items():
        if not (isinstance(g, dict) and g.get("kind") == "waves"):
            continue
        worst_dev, worst_spread, outside = 0.0, 0, 0
        for w in g["waves"]:
            before, after = w["before"], w["after"]
            # The live servers' capacities: the lattice step nearest to
            # what each holds after the wave (widths of the full-capacity
            # members hardly depend on them; see waterfill.py).
            cap = quotas.infer_capacity(after, active, c.live_idx)
            worst_dev = max(
                worst_dev, waterfill.full_member_deviation(before, after, cap, full)
            )
            worst_spread = max(worst_spread, int(np.ptp(after[full])))
            outside += live_outside_bracket(before, after, active, c.live_idx, w["n"])
        run.check(f"{name}.full_member_deviation_seats", worst_dev, DEVIATION_LIMIT_SEATS)
        run.check(f"{name}.full_member_spread_seats", worst_spread, SPREAD_LIMIT_SEATS)
        run.check(f"{name}.live_server_outside_lattice_share", outside, 0)
