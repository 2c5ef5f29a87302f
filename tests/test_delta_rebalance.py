"""Incremental (delta) rebalance: residual-capacity solves over the
displaced set, warm-started from the previous plan's potentials.

The contract under test (ISSUE 8 / README "Incremental rebalance"):

- A churn event re-solves ONLY the displaced objects; undisplaced objects
  never move (``test_delta_moves_exactly_the_displaced_set``).
- The delta landing matches the integer fair quotas a full solve targets,
  so transport cost stays within ``delta_audit_ratio`` of the full-solve
  ideal (``test_delta_cost_parity_with_full_solve``).
- Every gate that routes an event back to the full pipeline works:
  displaced fraction over ``delta_threshold``, ``max_delta_solves``
  staleness bound, a tripped transport-cost audit, ``delta=False`` /
  ``delta=True`` overrides, and the zero-schedulable-capacity outage mode.
- The epoch-discard consistency check covers the delta path exactly like
  the full path: a directory that changed under the solve discards it.
- Warm-started solver calls are semantically equivalent to cold ones
  (log-domain reference parity, including the wide-cost-range per-row
  gauge regime), so warm-starting is purely a convergence accelerator.
"""

import asyncio
import time

import jax.numpy as jnp
import numpy as np
import pytest

from rio_tpu import ObjectId
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement
from rio_tpu.ops import integer_fair_quotas, residual_capacity_assign
from rio_tpu.ops.scaling import scaling_sinkhorn
from rio_tpu.ops.sinkhorn import sinkhorn


class _Member:
    def __init__(self, address: str, active: bool = True) -> None:
        self.address = address
        self.active = active


def _members(n, dead=()):
    return [_Member(f"10.7.0.{i}:5000", i not in dead) for i in range(n)]


async def _seeded(n_obj, n_nodes, **kw):
    """Provider with ``n_obj`` seated objects and a committed plan."""
    p = JaxObjectPlacement(node_axis_size=n_nodes, **kw)
    p.sync_members(_members(n_nodes))
    await p.assign_batch([ObjectId("T", str(i)) for i in range(n_obj)])
    await p.rebalance(delta=False)  # pay compiles, commit the PlanState
    return p


def _congestion(p, n_obj):
    """Quadratic congestion of the seating vs the integer-quota ideal."""
    m = p._node_axis
    counts = np.asarray(
        [len(p._by_node.get(i, ())) for i in range(m)], np.float64
    )
    cap_alive = np.zeros(m)
    for s in p._nodes.values():
        cap_alive[s.index] = s.capacity if (s.alive and not s.cordoned) else 0.0
    quota = integer_fair_quotas(cap_alive, n_obj).astype(np.float64)
    safe = np.maximum(cap_alive, 1e-9)
    return float(np.sum(counts**2 / safe)), float(np.sum(quota**2 / safe))


# --------------------------------------------------------- residual helpers


def test_integer_fair_quotas_sum_exactly_and_respect_zeros():
    cap = np.asarray([3.0, 1.0, 0.0, 2.0])
    for n in (0, 1, 7, 100, 12345):
        q = integer_fair_quotas(cap, n)
        assert q.sum() == n
        assert q[2] == 0  # zero capacity never gets a seat
        # Largest-remainder shares stay within 1 of the real-valued share.
        exact = cap / cap.sum() * n
        assert np.all(np.abs(q - exact) < 1.0)


def test_integer_fair_quotas_all_zero_capacity_is_empty():
    q = integer_fair_quotas(np.zeros(4), 10)
    assert q.sum() == 0  # degenerate: nothing schedulable, nothing promised


def test_residual_capacity_assign_fills_residuals_exactly():
    residual = np.asarray([2, 0, 3, 1])
    score = np.asarray([0.5, 9.9, 0.1, 0.7])
    out = residual_capacity_assign(score, residual)
    assert out.shape == (6,)
    assert np.array_equal(np.bincount(out, minlength=4), residual)
    # Better-scored nodes fill first (displaced objects are interchangeable
    # under the flat cost model, so only the per-node counts are binding —
    # but the ordering keeps the fill deterministic).
    assert out[0] == 2


# ------------------------------------------------------------ delta solves


@pytest.mark.parametrize("mode", ["sinkhorn", "scaling", "greedy"])
async def test_delta_moves_exactly_the_displaced_set(mode):
    n_obj, n_nodes = 512, 8
    p = await _seeded(n_obj, n_nodes, mode=mode)
    pre = dict(p._placements)
    dead_idx = p._nodes[_members(n_nodes)[0].address].index
    p.sync_members(_members(n_nodes, dead={0}))
    moved = await p.rebalance()
    assert p.stats.mode == f"{mode}+delta"
    assert p.stats.displaced == sum(1 for v in pre.values() if v == dead_idx)
    assert moved == p.stats.displaced
    # ZERO undisplaced moves: objects off the dead node kept their seats.
    assert all(
        p._placements[k] == v for k, v in pre.items() if v != dead_idx
    )
    # Nothing seated on the dead node; survivors at integer fair quotas.
    counts = [len(p._by_node.get(i, ())) for i in range(p._node_axis)]
    assert counts[dead_idx] == 0
    num, den = _congestion(p, n_obj)
    assert num <= 1.05 * den


async def test_delta_cost_parity_with_full_solve():
    """Same churn event, delta vs full: identical per-node seat counts
    (both land on the integer fair quotas), so cost parity is exact."""
    n_obj, n_nodes = 600, 6
    results = {}
    for delta in (True, False):
        p = await _seeded(n_obj, n_nodes, mode="sinkhorn")
        p.sync_members(_members(n_nodes, dead={1}))
        await p.rebalance(delta=delta)
        num, den = _congestion(p, n_obj)
        results[delta] = num
        assert num <= 1.05 * den
    assert results[True] <= 1.05 * results[False]


async def test_delta_threshold_routes_big_events_to_full_solve():
    # Killing 1 of 3 nodes displaces ~33% > threshold 10% -> full path.
    p = await _seeded(300, 3, mode="sinkhorn", delta_threshold=0.10)
    p.sync_members(_members(3, dead={0}))
    await p.rebalance()
    assert "+delta" not in p.stats.mode
    assert p._plan is not None and p._plan.delta_solves == 0


async def test_delta_threshold_zero_disables_deltas():
    p = await _seeded(256, 8, mode="sinkhorn", delta_threshold=0.0)
    p.sync_members(_members(8, dead={0}))
    await p.rebalance()
    assert "+delta" not in p.stats.mode


async def test_delta_true_overrides_threshold_false_forces_full():
    p = await _seeded(300, 3, mode="sinkhorn", delta_threshold=0.0)
    p.sync_members(_members(3, dead={0}))
    moved = await p.rebalance(delta=True)  # force past every gate
    assert p.stats.mode == "sinkhorn+delta"
    assert moved == p.stats.displaced > 0
    p.sync_members(_members(3, dead={0, 1}))
    await p.rebalance(delta=False)  # force the full pipeline
    assert "+delta" not in p.stats.mode


async def test_max_delta_solves_forces_periodic_full_solve():
    p = await _seeded(512, 8, mode="sinkhorn", max_delta_solves=1)
    p.sync_members(_members(8, dead={0}))
    await p.rebalance()
    assert p.stats.mode == "sinkhorn+delta"
    assert p._plan.delta_solves == 1
    p.sync_members(_members(8, dead={0, 1}))
    await p.rebalance()  # staleness bound trips -> full re-solve
    assert "+delta" not in p.stats.mode
    assert p._plan.delta_solves == 0  # full solve resets the counter


async def test_tripped_audit_marks_plan_stale_next_solve_full():
    # An impossible audit bound (<1.0) trips on any delta, marking the
    # plan stale; the NEXT churn event must go through the full pipeline.
    p = await _seeded(512, 8, mode="sinkhorn", delta_audit_ratio=0.5)
    p.sync_members(_members(8, dead={0}))
    await p.rebalance()
    assert p.stats.mode == "sinkhorn+delta"
    assert p._plan.stale
    p.sync_members(_members(8, dead={0, 1}))
    await p.rebalance()
    assert "+delta" not in p.stats.mode
    assert not p._plan.stale


async def test_epoch_discard_mid_delta_leaves_directory_untouched():
    p = await _seeded(512, 8, mode="sinkhorn")
    plan_before = p._plan
    p.sync_members(_members(8, dead={0}))
    pre = dict(p._placements)

    real_refresh = p._class_refresh

    def racing_refresh(*a, **kw):
        # Simulate churn landing while the solver thread runs: any epoch
        # bump (allocation, update, sibling solve) must discard this solve.
        p._epoch += 1
        return real_refresh(*a, **kw)

    p._class_refresh = racing_refresh
    moved = await p.rebalance()
    assert moved == 0
    assert p.stats.discarded
    assert p.stats.mode == "sinkhorn+delta"
    assert dict(p._placements) == pre  # nothing applied
    assert p._plan is plan_before  # plan not replaced by a discarded solve
    # The event is still serviceable: a clean retry lands normally.
    p._class_refresh = real_refresh
    moved = await p.rebalance()
    assert not p.stats.discarded and moved > 0


async def test_no_schedulable_capacity_outage_then_recovery():
    p = await _seeded(256, 4, mode="sinkhorn")
    pre = dict(p._placements)
    p.sync_members(_members(4, dead={0, 1, 2, 3}))
    moved = await p.rebalance()
    # Total outage: reshuffling seats among dead nodes is pure churn —
    # stay put (delta path must NOT engage on the degenerate shape).
    assert moved == 0
    assert p.stats.mode.endswith("+no_capacity")
    assert dict(p._placements) == pre
    p.sync_members(_members(4))
    moved = await p.rebalance()
    assert not p.stats.mode.endswith("+no_capacity")
    num, den = _congestion(p, 256)
    assert num <= 1.05 * den


async def test_node_return_rebalances_overflow_onto_it():
    """A RETURNING node shrinks survivor quotas; the over-quota overflow
    (and only it) re-seats onto the recovered capacity."""
    p = await _seeded(400, 4, mode="sinkhorn")
    p.sync_members(_members(4, dead={0}))
    await p.rebalance()
    pre = dict(p._placements)
    p.sync_members(_members(4))  # node 0 comes back
    moved = await p.rebalance()
    if "+delta" in p.stats.mode:
        # Overflow-only displacement: ~n/4 objects move onto the returnee.
        assert moved == p.stats.displaced
        assert moved <= 110  # ~100 expected, never a global reshuffle
    back_idx = p._nodes[_members(4)[0].address].index
    assert len(p._by_node.get(back_idx, ())) > 0
    undisplaced_kept = sum(
        1 for k, v in pre.items() if p._placements[k] == v
    )
    assert undisplaced_kept >= len(pre) - moved


@pytest.mark.parametrize("priced", [False, True])
async def test_a_repriced_survivor_sheds_its_overflow_without_the_array_snapshot(priced):
    """A survivor over its quota (a load derate shrank its share; a node
    returned) used to send the whole event through the O(N) array snapshot:
    a list of every key under the lock, on the loop, per event (0.35 s a
    million rows) — the directory loading the very loop whose lag had
    re-priced the node. Under the flat cost model the rows of a node are
    interchangeable, so the O(displaced) snapshot sheds the newest
    ``counts - quota`` of them; with an ``object_costs`` hook the ranks
    decide, and the array route still runs."""
    hook = (lambda keys: np.ones(len(keys), np.float32)) if priced else None
    p = await _seeded(400, 4, mode="sinkhorn", object_costs=hook)
    array_route = []
    real = p._delta_solve
    p._delta_solve = lambda *a, **k: array_route.append(1) or real(*a, **k)
    pre = dict(p._placements)
    node = p._nodes[_members(4)[1].address]
    node.reported_derate = 0.5  # what sync_load does on a lattice step
    moved = await p.rebalance()
    assert p.stats.mode == "sinkhorn+delta" and not p.stats.discarded
    assert bool(array_route) is priced
    # 400 over capacities (1, 0.5, 1, 1): the re-priced node keeps 57 of its
    # 100 rows; the 43 it sheds are all that moves, and all of them were its.
    assert moved == p.stats.displaced == 43
    assert len(p._by_node[node.index]) == 57
    left = {k for k, v in pre.items() if p._placements[k] != v}
    assert len(left) == 43 and all(pre[k] == node.index for k in left)
    counts = sorted(len(p._by_node[j]) for j in range(4))
    assert counts == [57, 114, 114, 115]
    if not priced:
        # The newest of its rows went: the tail of the node's own order.
        before = [k for k, v in pre.items() if v == node.index]
        assert left <= set(before) and len(before) == 100
    # And back: the node returns to full price and is refilled, fast route.
    array_route.clear()
    node.reported_derate = 1.0
    assert await p.rebalance() == p.stats.displaced == 43
    assert not array_route or priced
    assert sorted(len(p._by_node[j]) for j in range(4)) == [100, 100, 100, 100]


# ---------------------------------------------------- warm-start parity


def _balanced_problem(key, n, m, scale=1.0):
    rng = np.random.default_rng(key)
    cost = rng.uniform(0.0, scale, size=(n, m)).astype(np.float32)
    mass = np.ones((n,), np.float32)
    cap = (np.ones((m,), np.float32) * n / m).astype(np.float32)
    return jnp.asarray(cost), jnp.asarray(mass), jnp.asarray(cap)


def test_warm_start_from_converged_is_a_fixed_point():
    cost, mass, cap = _balanced_problem(0, 96, 6)
    f0, g0, err0 = sinkhorn(cost, mass, cap, eps=0.05, n_iters=200)
    f1, g1, err1 = sinkhorn(cost, mass, cap, eps=0.05, n_iters=4, g_init=g0)
    # 4 warm iterations from the converged dual == converged.
    assert float(err1) <= float(err0) + 1e-4
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=1e-3)


def test_warm_start_accelerates_after_perturbation():
    cost, mass, cap = _balanced_problem(1, 128, 8)
    _f, g_conv, _e = sinkhorn(cost, mass, cap, eps=0.05, n_iters=200)
    # Perturb capacity (one node derated) — the churn shape deltas see.
    cap2 = np.asarray(cap).copy()
    cap2[0] *= 0.5
    cap2 = jnp.asarray(cap2 / cap2.sum() * np.asarray(mass).sum())
    _f, _g, err_warm = sinkhorn(cost, mass, cap2, eps=0.05, n_iters=8, g_init=g_conv)
    _f, _g, err_cold = sinkhorn(cost, mass, cap2, eps=0.05, n_iters=8)
    assert float(err_warm) <= float(err_cold) + 1e-5


def test_scaling_warm_start_matches_log_domain_reference():
    cost, mass, cap = _balanced_problem(2, 80, 5)
    _f, g_seed, _e = sinkhorn(cost, mass, cap, eps=0.05, n_iters=30)
    fs, gs, _ = scaling_sinkhorn(cost, mass, cap, eps=0.05, n_iters=40, g_init=g_seed)
    fl, gl, _ = sinkhorn(cost, mass, cap, eps=0.05, n_iters=40, g_init=g_seed)
    # Potentials agree up to the shared constant gauge.
    shift = float(np.median(np.asarray(gs) - np.asarray(gl)))
    np.testing.assert_allclose(
        np.asarray(gs) - shift, np.asarray(gl), atol=5e-2
    )


def test_scaling_warm_start_survives_wide_cost_ranges():
    """Per-row gauge shift must survive warm starts: cost-range/eps >> 88
    underflows a global-shift scaling form to all-zero kernels (the r3
    regression) — warm-seeded or not."""
    rng = np.random.default_rng(3)
    n, m = 64, 4
    row_scale = np.exp(rng.uniform(0.0, 8.0, size=(n, 1)))
    cost = jnp.asarray((rng.uniform(0.0, 1.0, (n, m)) * row_scale).astype(np.float32))
    mass = jnp.ones((n,), jnp.float32)
    cap = jnp.ones((m,), jnp.float32) * (n / m)
    _f, g_seed, _e = sinkhorn(cost, mass, cap, eps=0.05, n_iters=30)
    fs, gs, err = scaling_sinkhorn(
        cost, mass, cap, eps=0.05, n_iters=60, g_init=g_seed
    )
    assert np.all(np.isfinite(np.asarray(fs)))
    assert np.all(np.isfinite(np.asarray(gs)))
    fl, gl, err_l = sinkhorn(cost, mass, cap, eps=0.05, n_iters=60, g_init=g_seed)
    # Marginal violation tracks the log-domain reference — no divergence.
    assert float(err) <= 2.0 * float(err_l) + 1e-3


def test_warm_start_with_nonfinite_seed_entries_cold_fills():
    # A plan solved before a node registered carries -inf for it; warm
    # starts must treat those entries as cold (0), not propagate them.
    cost, mass, cap = _balanced_problem(4, 60, 6)
    _f, g0, _e = sinkhorn(cost, mass, cap, eps=0.05, n_iters=30)
    g_hole = np.asarray(g0).copy()
    g_hole[2] = -np.inf
    for solver in (sinkhorn, scaling_sinkhorn):
        f, g, err = solver(
            cost, mass, cap, eps=0.05, n_iters=40, g_init=jnp.asarray(g_hole)
        )
        assert np.all(np.isfinite(np.asarray(g)))
        assert float(err) < 1.0


# ------------------------------------------------------------- churn soak


async def _churn_ab(n_obj, n_nodes):
    p = await _seeded(n_obj, n_nodes, mode="sinkhorn")
    # Warm both code paths' compiles before timing.
    p.sync_members(_members(n_nodes, dead={0}))
    t0 = time.perf_counter()
    await p.rebalance(delta=False)
    full_ms = (time.perf_counter() - t0) * 1e3
    p.sync_members(_members(n_nodes, dead={0, 1}))
    await p.rebalance()
    assert p.stats.mode == "sinkhorn+delta"
    dead = {0, 1, 2}
    p.sync_members(_members(n_nodes, dead=dead))
    t0 = time.perf_counter()
    moved = await p.rebalance()
    delta_ms = (time.perf_counter() - t0) * 1e3
    assert p.stats.mode == "sinkhorn+delta"
    assert moved == p.stats.displaced
    num, den = _congestion(p, n_obj)
    assert num <= 1.05 * den
    return full_ms, delta_ms


async def test_churn_delta_beats_full_small():
    """Tier-1 variant of the 1M soak: the delta event must not regress to
    full-solve cost (the hard >=10x bar is measured at 1M by
    ``bench.py --delta``, where the O(N) snapshot dominates)."""
    full_ms, delta_ms = await _churn_ab(20_000, 16)
    assert delta_ms < full_ms  # strictly cheaper even at toy scale


@pytest.mark.slow
async def test_churn_soak_1m_delta_speedup():
    """1M x 64 churn soak (the bench.py --delta acceptance shape): a
    single-node death reacts >=10x faster through the delta path, with a
    sequence of deltas staying quota-exact."""
    full_ms, delta_ms = await _churn_ab(1_048_576, 64)
    assert delta_ms * 10 <= full_ms
