"""Affinity payoff: hierarchical + AffinityTracker must EARN its complexity.

r3 review, item 5: the affinity loop was fully wired (dispatch-observed
tracker, tracker-carrying provider) but nothing demonstrated that it
produces *better placements* than flat greedy on any workload metric.

The workload here is the one locality exists for: every object has a warm
HOME (where its state lives) and a warm SECONDARY (a node that also served
it — replica reads, a previous seat, a failover). Homes die; the placement
question is where the displaced state lands:

* flat greedy re-seats the displaced share by load headroom only — the
  warm secondary is hit ~1/survivors of the time;
* hierarchical + tracker scores ``obj_feat . node_feat`` where the
  object's feature is the request-weighted EMA of the nodes that served
  it — the displaced object is PULLED to its secondary, and a state
  reload (a landing on a node that never served the object) is avoided.

Metrics asserted, same inputs for both modes:
  (a) locality hit rate of displaced objects (landed on their secondary);
  (b) cold state reloads (landed somewhere that never served them);
  (c) mean assigned affinity score.

Also locks the mode="auto" rule: a provider constructed with an
AffinityTracker resolves auto -> hierarchical (the only mode that consumes
the signal; cost O(N*(G+S+d)) is accelerator-independent).
"""

import numpy as np

from rio_tpu import ObjectId, ObjectPlacementItem
from rio_tpu.object_placement.jax_placement import (
    AffinityTracker,
    JaxObjectPlacement,
)

M = 16  # nodes
PER_NODE = 30  # objects per node
N = M * PER_NODE
DEAD = [0, 1, 2, 3]  # the churn event: these homes die


class _Member:
    def __init__(self, addr, active):
        self._addr, self.active = addr, active

    def address(self):
        return self._addr


def _addr(i: int) -> str:
    return f"10.0.0.{i}:5000"


def _workload():
    """(key, home, secondary) triples; secondaries uniform over survivors.

    Capacity math is exactly feasible: 120 displaced objects spread over 12
    survivors = 10 each, matching the survivors' fair-share headroom
    (480/12 = 40 vs 30 currently seated).
    """
    survivors = [i for i in range(M) if i not in DEAD]
    out = []
    for i in range(N):
        home = i % M
        sec = survivors[(i * 7 + 3) % len(survivors)]
        if sec == home:
            sec = survivors[(i * 7 + 4) % len(survivors)]
        out.append((f"Obj.{i}", home, sec))
    return out


async def _seed(p: JaxObjectPlacement, work) -> None:
    for key, home, _sec in work:
        t, _, i = key.partition(".")
        await p.update(ObjectPlacementItem(ObjectId(t, i), _addr(home)))


def _warm(tracker: AffinityTracker, work) -> None:
    """Interleaved 3:1 home:secondary traffic (how real request streams
    arrive); the EMA converges to the traffic-share mix, leaving a strong
    home component and a clearly detectable secondary one."""
    for key, home, sec in work:
        for _ in range(4):
            for _ in range(3):
                tracker.observe(key, _addr(home))
            tracker.observe(key, _addr(sec))


def _kill(p: JaxObjectPlacement) -> None:
    p.sync_members([_Member(_addr(i), i not in DEAD) for i in range(M)])


def _metrics(p: JaxObjectPlacement, work) -> dict:
    hits = cold = moved_survivor = 0
    for key, home, sec in work:
        new = p._node_order[p._placements[key]]
        if home in DEAD:
            if new == _addr(sec):
                hits += 1
            elif new != _addr(home):
                cold += 1
        elif new != _addr(home):
            moved_survivor += 1
            cold += 1
    displaced = sum(1 for _, home, _s in work if home in DEAD)
    return {
        "displaced": displaced,
        "locality_hits": hits,
        "hit_rate": hits / displaced,
        "cold_reloads": cold,
        "survivor_moves": moved_survivor,
    }


async def test_hierarchical_affinity_beats_flat_greedy_on_churn():
    work = _workload()

    # Flat greedy baseline (what auto picks on CPU without a signal).
    pg = JaxObjectPlacement(node_axis_size=M, mode="greedy")
    for i in range(M):
        pg.register_node(_addr(i))
    await _seed(pg, work)
    _kill(pg)
    await pg.rebalance()
    mg = _metrics(pg, work)

    # Hierarchical + tracker on identical placements and churn; mode="auto"
    # must resolve to hierarchical because the signal exists.
    tracker = AffinityTracker()
    ph = JaxObjectPlacement(node_axis_size=M, affinity_tracker=tracker)
    for i in range(M):
        ph.register_node(_addr(i))
    await _seed(ph, work)
    _warm(tracker, work)
    _kill(ph)
    await ph.rebalance()
    mh = _metrics(ph, work)
    assert ph.stats.mode == "hierarchical", ph.stats.mode

    # Every displaced object left its dead home in both modes.
    for m in (mg, mh):
        assert m["displaced"] == len(DEAD) * PER_NODE

    # (a) locality: the tracker must multiply the hit rate, not nudge it.
    assert mh["hit_rate"] >= 3 * max(mg["hit_rate"], 1 / (M - len(DEAD))), (
        mh,
        mg,
    )
    assert mh["hit_rate"] >= 0.5, mh
    # (b) serving metric: cold state reloads well under flat greedy's. The
    # exact ratio is jax-version sensitive (0.44 on jax>=0.6, 0.53 on
    # 0.4.37); the contract is a large relative win, not the third decimal.
    assert mh["cold_reloads"] <= 0.6 * max(mg["cold_reloads"], 1), (mh, mg)
    # (c) assigned affinity score (the solver's own objective, with REAL
    # affinity): hierarchical must strictly win.
    keys = [k for k, _h, _s in work]

    def mean_score(p):
        of = tracker.obj_features(keys)
        nf = tracker.node_features([_addr(i) for i in range(M)])
        idx = np.asarray([p._placements[k] for k in keys])
        return float((of * nf[idx]).sum(axis=1).mean())

    # Both keep survivors home, so the win concentrates in the displaced
    # quarter of objects (measured 0.77 vs 0.68 overall).
    assert mean_score(ph) > mean_score(pg) + 0.05, (
        mean_score(ph),
        mean_score(pg),
    )

    # Load safety: affinity never overrides capacity — dead nodes empty,
    # survivors within fair-share slack.
    loads = np.bincount(list(ph._placements.values()), minlength=M)
    assert loads[DEAD].sum() == 0
    assert loads.max() <= 1.5 * (N / (M - len(DEAD)))


async def test_auto_mode_without_signal_is_unchanged():
    p = JaxObjectPlacement(node_axis_size=M)
    for i in range(4):
        p.register_node(_addr(i))
    for i in range(64):
        await p.update(ObjectPlacementItem(ObjectId("T", str(i)), _addr(i % 4)))
    await p.rebalance()
    # On this CPU host the signal-free auto still resolves to greedy.
    assert p.stats.mode == "greedy"
