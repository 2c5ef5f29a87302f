"""The three plain references against cases worked by hand."""

import numpy as np

from benchmark.reference import aggregate, quotas, waterfill


def test_largest_remainder_hand_case():
    # 10 seats over capacities 1, 1, 1: shares 3.33 each, one unit left.
    q = quotas.largest_remainder(np.array([1.0, 1.0, 1.0]), 10)
    assert sorted(q.tolist()) == [3, 3, 4]
    # 7 seats over 2, 1, 0: shares 4.67, 2.33, 0 -> floors 4, 2, the unit to 0.67.
    assert quotas.largest_remainder(np.array([2.0, 1.0, 0.0]), 7).tolist() == [5, 2, 0]


def test_ties_may_fall_either_way_and_nothing_else():
    cap = np.array([1.0, 1.0, 1.0])
    for loads in ([4, 3, 3], [3, 4, 3], [3, 3, 4]):
        assert quotas.miss(np.array(loads), cap) == 0
    assert quotas.miss(np.array([5, 3, 2]), cap) == 2  # one over its ceiling, one under its floor
    # 2.33 is no tie with 4.67: the unit belongs to the first node.
    assert quotas.miss(np.array([4, 3, 0]), np.array([2.0, 1.0, 0.0])) == 2
    assert quotas.miss(np.array([5, 2, 0]), np.array([2.0, 1.0, 0.0])) == 0


def test_a_seat_on_an_inactive_node_is_a_miss():
    assert quotas.miss(np.array([4, 4, 1]), np.array([1.0, 1.0, 0.0])) >= 1


def test_derated_lattice_vector_is_recovered_from_loads():
    n, m = 1_048_576, 1024
    cap = np.ones(m)
    cap[:37] = 0.0  # inactive
    live = np.arange(m - 8, m)
    cap[live] = [1.0, 0.875, 0.5, 0.125, 1.0, 0.75, 0.25, 1.0]
    loads = quotas.largest_remainder(cap, n)
    got = quotas.infer_capacity(loads, cap > 0, live)
    assert np.array_equal(got, cap)
    assert quotas.miss(loads, got) == 0
    # A solve that computed its shares in bfloat16 misses by seats.
    import ml_dtypes

    low = quotas.shares(cap, n).astype(ml_dtypes.bfloat16).astype(np.float64)
    assert quotas.miss(np.rint(low).astype(np.int64), cap, n=n) > 100


def test_lattice_where_every_node_is_a_live_server():
    cap = np.array([0.5, 1.0, 1.0, 0.75, 1.0, 1.0, 1.0, 0.875])
    loads = quotas.largest_remainder(cap, 100_000)
    got = quotas.infer_capacity(loads, np.ones(8, bool), np.arange(8))
    assert quotas.miss(loads, got) == 0
    assert np.allclose(got / got.max(), cap / cap.max())


def test_waterfill_with_over_fair_nodes():
    # 4 equal nodes hold 10, 10, 10, 30; 20 arrive: fair is 20 each, the
    # last is over it and takes none, the others split the batch.
    w = waterfill.widths(np.array([10.0, 10, 10, 30]), np.ones(4), 20)
    assert np.allclose(w, [20 / 3, 20 / 3, 20 / 3, 0.0])
    inc = waterfill.increments(np.array([10.0, 10, 10, 30]), np.ones(4), 20)
    assert inc.sum() == 20 and inc[3] == 0 and np.all(np.abs(inc[:3] - 20 / 3) < 1)
    # Laid out in another order a node still takes the floor or the ceiling.
    inc2 = waterfill.increments(np.array([10.0, 10, 10, 30]), np.ones(4), 20, order=[2, 3, 0, 1])
    assert np.all(np.abs(inc2 - w) < 1)


def test_waterfill_dead_node_and_deviation():
    load = np.array([100.0, 101, 100, 50, 0])
    cap = np.array([1.0, 1.0, 1.0, 0.5, 0.0])
    after = load + waterfill.increments(load, cap, 64)
    assert after[4] == 0
    full = np.array([0, 1, 2])
    assert waterfill.full_member_deviation(load, after, cap, full) < 1.0
    skewed = after.copy()
    skewed[0] += 4
    skewed[1] -= 4
    assert waterfill.full_member_deviation(load, skewed, cap, full) > 3.0


def test_aggregate_replay_and_a_failed_request():
    acked = [("m1", "tag0", 3.0), ("m1", "tag1", 5.0), ("m2", "tag0", 1.0)]
    want = aggregate.replay(acked)
    assert want["m1"].row() == (2, 8.0, 3.0, 5.0)
    assert want["m1.tag1"].row() == (1, 5.0, 5.0, 5.0)
    stored = {k: v.row() for k, v in want.items()}
    assert aggregate.mismatches(acked, [], stored) == 0
    # A failed request may have reached the name and not the tag.
    failed = [("m1", "tag0", 7.0)]
    half = dict(stored)
    half["m1"] = (3, 15.0, 3.0, 7.0)
    assert aggregate.mismatches(acked, failed, half) == 0
    assert aggregate.mismatches(acked, failed, stored) == 0
    # A lost acknowledged sample is a mismatch, failed request or not.
    lost = dict(stored)
    lost["m2"] = (0, 0.0, 0.0, 0.0)
    assert aggregate.mismatches(acked, failed, lost) == 1
    twice = dict(stored)
    twice["m1"] = (4, 22.0, 3.0, 7.0)  # applied twice: beyond the two admissible values
    assert aggregate.mismatches(acked, failed, twice) == 1


def test_a_live_servers_wave_share_is_bracketed_whatever_the_derates():
    """Eight live servers on steps of the lattice that differ, with loads
    left from other steps: each increment of the float64 waterfill lies in
    the bracket the audit draws without knowing any step; one that no
    combination of steps explains does not."""
    from benchmark.audits.waves_balance import live_outside_bracket

    rng = np.random.default_rng(7)
    m, n_new = 1024, 65536
    live = np.arange(8)
    for _ in range(200):
        active = np.ones(m, bool)
        active[rng.choice(np.arange(8, m), 21, replace=False)] = False
        true_cap = active.astype(np.float64)
        true_cap[live] = quotas.LATTICE[rng.integers(0, 8, 8)]
        before = np.where(active, 2600 + rng.integers(0, 2, m), 0).astype(np.float64)
        before[live] = np.round(2600 * quotas.LATTICE[rng.integers(0, 8, 8)])
        after = before + waterfill.increments(before, true_cap, n_new)
        assert live_outside_bracket(before, after, active, live, n_new) == 0
    # A server that holds more than any fair share takes nothing of a batch.
    before[3] = 2800
    after = before + waterfill.increments(before, true_cap, n_new)
    assert live_outside_bracket(before, after, active, live, n_new) == 0
    after[3] += 40
    assert live_outside_bracket(before, after, active, live, n_new) == 1
