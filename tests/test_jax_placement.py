"""JaxObjectPlacement: trait parity + batched/device behaviors.

Trait semantics mirror the reference backend matrix
(``rio-rs/tests/object_placement_backend.rs``); the batched/rebalance paths
are rio-tpu additions.
"""

import asyncio
import gc
from types import SimpleNamespace

import numpy as np
import pytest

from rio_tpu import ObjectId, ObjectPlacementItem
from rio_tpu.errors import NoSchedulableCapacity
from rio_tpu.object_placement import LocalObjectPlacement
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement
from rio_tpu.object_placement.persistent import PersistentJaxObjectPlacement
from tests.test_soak_random_ops import _check_invariants


def _provider(nodes=4, **kw):
    p = JaxObjectPlacement(node_axis_size=16, **kw)
    for i in range(nodes):
        p.register_node(f"10.0.0.{i}:5000")
    return p


async def test_assign_batch_empty_cluster_raises_no_schedulable_capacity():
    """No registered (or no live) nodes is a documented, typed error — not
    the bare ValueError the solver guts used to leak — and it still
    satisfies ``except ValueError`` for callers written against that."""
    p = JaxObjectPlacement(node_axis_size=16)
    with pytest.raises(NoSchedulableCapacity, match="register_node"):
        await p.assign_batch([ObjectId("Game", "g0")])
    assert issubclass(NoSchedulableCapacity, ValueError)
    # (All-registered-but-dead is NOT this error: the all-dead blip still
    # seats on real nodes — see ``_least_loaded_spread``.)


async def test_trait_parity_update_lookup_remove():
    p = _provider()
    oid = ObjectId("MetricAggregator", "instance-1")
    assert await p.lookup(oid) is None
    await p.update(ObjectPlacementItem(oid, "10.0.0.1:5000"))
    assert await p.lookup(oid) == "10.0.0.1:5000"
    await p.update(ObjectPlacementItem(oid, "10.0.0.2:5000"))  # upsert
    assert await p.lookup(oid) == "10.0.0.2:5000"
    await p.remove(oid)
    assert await p.lookup(oid) is None


async def test_trait_parity_clean_server():
    p = _provider()
    a = ObjectId("T", "a")
    b = ObjectId("T", "b")
    await p.update(ObjectPlacementItem(a, "10.0.0.1:5000"))
    await p.update(ObjectPlacementItem(b, "10.0.0.2:5000"))
    await p.clean_server("10.0.0.1:5000")
    assert await p.lookup(a) is None
    assert await p.lookup(b) == "10.0.0.2:5000"


async def test_assign_batch_spreads_and_is_sticky():
    p = _provider(nodes=4)
    oids = [ObjectId("Game", str(i)) for i in range(400)]
    addrs = await p.assign_batch(oids)
    counts = {}
    for a in addrs:
        counts[a] = counts.get(a, 0) + 1
    assert len(counts) == 4
    assert max(counts.values()) <= 2 * 100
    # Re-assigning returns identical seats (no churn).
    again = await p.assign_batch(oids)
    assert addrs == again
    assert p.count() == 400


async def test_assign_batch_avoids_dead_nodes():
    p = _provider(nodes=4)

    class M:
        def __init__(self, addr, active):
            self._addr, self.active = addr, active

        def address(self):
            return self._addr

    members = [M(f"10.0.0.{i}:5000", i != 2) for i in range(4)]
    p.sync_members(members)
    addrs = await p.assign_batch([ObjectId("T", str(i)) for i in range(100)])
    assert "10.0.0.2:5000" not in addrs


async def test_rebalance_sinkhorn_levels_skew():
    p = _provider(nodes=4)
    # Pile everything onto one node, then re-solve.
    for i in range(200):
        await p.update(ObjectPlacementItem(ObjectId("T", str(i)), "10.0.0.0:5000"))
    moved = await p.rebalance(mode="sinkhorn")
    assert moved > 0
    addrs = await p.lookup_batch([ObjectId("T", str(i)) for i in range(200)])
    counts = np.unique(addrs, return_counts=True)[1]
    assert counts.max() <= 2 * 200 / 4
    assert p.stats.n_objects == 200
    assert p.stats.solve_ms > 0


async def test_rebalance_greedy_mode():
    p = _provider(nodes=4)
    for i in range(128):
        await p.update(ObjectPlacementItem(ObjectId("T", str(i)), "10.0.0.3:5000"))
    moved = await p.rebalance(mode="greedy")
    assert moved > 0
    addrs = await p.lookup_batch([ObjectId("T", str(i)) for i in range(128)])
    counts = np.unique(addrs, return_counts=True)[1]
    assert counts.max() <= 2 * 128 / 4


async def test_incremental_after_rebalance_uses_potentials():
    p = _provider(nodes=4)
    await p.assign_batch([ObjectId("T", str(i)) for i in range(64)])
    await p.rebalance(mode="sinkhorn")
    assert p._g is not None
    # New arrivals take the cached-potentials fast path.
    addrs = await p.assign_batch([ObjectId("U", str(i)) for i in range(32)])
    assert all(a.startswith("10.0.0.") for a in addrs)


async def test_potentials_survive_no_op_and_additive_churn():
    """``_g`` is versioned by the schedulable-node fingerprint, not nulled
    on every sync_members: a sync that changes nothing — and even a NEW
    node joining — keeps the cached potentials (the newcomer's entry is
    -inf, so the warm assign path conservatively never seats there until
    the next solve learns it)."""

    class M:
        def __init__(self, address, active=True):
            self.address = address
            self.active = active

    p = _provider(nodes=4)
    await p.assign_batch([ObjectId("T", str(i)) for i in range(64)])
    await p.rebalance(mode="sinkhorn")
    g = p._g
    assert g is not None
    members = [M(f"10.0.0.{i}:5000") for i in range(4)]
    p.sync_members(members)  # no liveness change
    assert p._g is g
    p.sync_members(members + [M("10.0.0.9:5000")])  # additive join
    assert p._g is g


async def test_dead_node_still_invalidates_potentials():
    """Regression guard for the fingerprint versioning: a node LEAVING the
    schedulable set (solved-over potentials now lie about live capacity)
    must still drop ``_g`` — both via sync_members and via cordon."""

    class M:
        def __init__(self, address, active=True):
            self.address = address
            self.active = active

    p = _provider(nodes=4)
    await p.assign_batch([ObjectId("T", str(i)) for i in range(64)])
    await p.rebalance(mode="sinkhorn")
    assert p._g is not None
    p.sync_members(
        [M(f"10.0.0.{i}:5000", active=(i != 2)) for i in range(4)]
    )
    assert p._g is None
    await p.rebalance(mode="sinkhorn")
    assert p._g is not None
    p.cordon("10.0.0.1:5000")
    assert p._g is None


async def test_node_axis_grows():
    p = JaxObjectPlacement(node_axis_size=2)
    for i in range(5):
        p.register_node(f"10.0.1.{i}:5000")
    addrs = await p.assign_batch([ObjectId("T", str(i)) for i in range(50)])
    assert len(set(addrs)) == 5


async def test_sync_members_with_real_member_objects():
    # Regression: Member.address is a property (str), not a method.
    from rio_tpu.cluster.storage import Member

    p = _provider(nodes=0)
    members = [Member.from_address(f"10.1.0.{i}:5000", active=(i != 1)) for i in range(3)]
    p.sync_members(members)
    assert set(p._nodes) == {f"10.1.0.{i}:5000" for i in range(3)}
    assert p._nodes["10.1.0.1:5000"].alive is False
    addrs = await p.assign_batch([ObjectId("T", str(i)) for i in range(40)])
    assert "10.1.0.1:5000" not in addrs
    assert set(addrs) == {"10.1.0.0:5000", "10.1.0.2:5000"}


async def test_rebalance_hierarchical_mode():
    """Two-level OT mode: valid, live-only, reasonably balanced placements."""
    placement = JaxObjectPlacement(mode="hierarchical", n_iters=15)
    for i in range(16):
        placement.register_node(f"10.1.0.{i}:70")
    ids = [ObjectId("H", str(i)) for i in range(800)]
    await placement.assign_batch(ids)
    await placement.clean_server("10.1.0.3:70")
    orphans = [i for i in ids if await placement.lookup(i) is None]
    await placement.assign_batch(orphans)
    moved = await placement.rebalance()
    assert moved >= 0
    counts: dict[str, int] = {}
    for oid in ids:
        addr = await placement.lookup(oid)
        assert addr is not None and addr != "10.1.0.3:70"
        counts[addr] = counts.get(addr, 0) + 1
    fair = len(ids) / 15
    assert max(counts.values()) < 2.5 * fair
    assert placement.stats.mode == "hierarchical"


async def test_full_rebalance_moves_only_displaced_share():
    """Churn-aware re-solve: killing 10% of nodes must move ~10% of objects.

    The stay-put discount (``move_cost``) makes the full ``rebalance()``
    prefer each object's current seat; only capacity pressure from the dead
    nodes forces moves (BASELINE.md row 4 — the reference re-places on
    lookup-miss only, so its analog never reshuffles healthy placements
    either; a TPU re-solve must not regress that).
    """
    n_nodes, n_objects = 20, 2000
    p = JaxObjectPlacement(mode="sinkhorn")
    for i in range(n_nodes):
        p.register_node(f"10.0.0.{i}:50")
    ids = [ObjectId("T", str(i)) for i in range(n_objects)]
    await p.assign_batch(ids)
    await p.rebalance()
    before = {str(i): await p.lookup(i) for i in ids}

    # 2 of 20 nodes die via gossip (placements stay, liveness flips).
    class M:
        def __init__(self, addr, active):
            self.address, self.active = addr, active

    p.sync_members(
        [M(f"10.0.0.{i}:50", active=i >= 2) for i in range(n_nodes)]
    )
    displaced = sum(
        1 for i in ids if before[str(i)] in (f"10.0.0.{j}:50" for j in range(2))
    )
    assert displaced > 0

    moved = await p.rebalance()
    assert p.stats.moved == moved
    # Moves are bounded by the displaced share plus slack for capacity
    # re-leveling (18 nodes absorbing the orphans shift fair shares a bit).
    assert moved <= int(1.5 * displaced) + n_nodes, (moved, displaced)
    # Every object lives on a live node, load stays capacity-sane.
    counts: dict[str, int] = {}
    for i in ids:
        addr = await p.lookup(i)
        assert addr is not None and not addr.startswith(("10.0.0.0:", "10.0.0.1:"))
        counts[addr] = counts.get(addr, 0) + 1
    fair = n_objects / (n_nodes - 2)
    assert max(counts.values()) < 2.0 * fair


async def test_second_rebalance_is_stationary():
    """With no churn between solves, a re-solve must move (almost) nothing."""
    p = _provider(nodes=8)
    ids = [ObjectId("T", str(i)) for i in range(800)]
    await p.assign_batch(ids)
    await p.rebalance()
    moved = await p.rebalance()
    assert moved <= len(ids) // 50, moved  # <=2% drift, not a reshuffle


@pytest.mark.slow
async def test_directory_scale_budgets():
    """1M-entry host directory: mutation paths must stay off O(total) scans.

    Budgets are generous (CI machines vary) but catch the O(N)-per-op
    regressions: clean_server via the per-node index is O(objects-on-node),
    lookups stay O(1).
    """
    import time

    p = JaxObjectPlacement(node_axis_size=64)
    for i in range(64):
        p.register_node(f"10.0.{i // 256}.{i % 256}:50")

    n = 1_000_000
    t0 = time.perf_counter()
    # Bulk insert through the same internal the trait paths use.
    for i in range(n):
        p._set_placement(f"T.{i}", i & 63)
    insert_s = time.perf_counter() - t0
    assert p.count() == n

    t0 = time.perf_counter()
    for i in range(0, n, 1000):
        assert (await p.lookup(ObjectId("T", str(i)))) is not None
    lookup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    await p.clean_server("10.0.0.7:50")
    clean_s = time.perf_counter() - t0
    assert p.count() == n - n // 64

    # Re-homing the orphans against cached-potential-free greedy path.
    assert insert_s < 30.0, insert_s
    assert lookup_s < 1.0, lookup_s
    assert clean_s < 2.0, clean_s


async def test_hierarchical_affinity_tracker_steers_placement():
    """Real locality signal through the feature hooks must steer the solve.

    AffinityTracker turns observed traffic into features; after observing
    each object on a "home" node, a hierarchical re-solve should send the
    vast majority home (vs ~1/M for the hashed-identity default) while the
    capacity marginals keep load balanced. This is the semantic-affinity
    hook the r3 review flagged: the 2-level OT now optimizes something real.
    """
    from rio_tpu.object_placement.jax_placement import AffinityTracker

    tracker = AffinityTracker(dim=32)
    p = JaxObjectPlacement(
        mode="hierarchical",
        n_iters=20,
        obj_features=tracker.obj_features,
        node_features=tracker.node_features,
    )
    nodes = [f"10.0.0.{i}:50" for i in range(16)]
    for a in nodes:
        p.register_node(a)
    ids = [ObjectId("T", str(i)) for i in range(320)]
    home = {str(ids[i]): nodes[i % 16] for i in range(320)}
    await p.assign_batch(ids)
    # weight=1.0 keeps alpha below 1 so the real EMA blend + cold-start
    # seeding + renormalization paths are exercised (two rounds converge
    # the feature toward the home embedding without pinning it outright).
    for _ in range(2):
        for k, a in home.items():
            tracker.observe(k, a, weight=1.0)
    await p.rebalance()

    hit = 0
    counts: dict[str, int] = {}
    for i in ids:
        a = await p.lookup(i)
        counts[a] = counts.get(a, 0) + 1
        if a == home[str(i)]:
            hit += 1
    fair = len(ids) / len(nodes)
    assert hit >= 0.75 * len(ids), hit  # measured ~92%; hashed default ~9%
    assert max(counts.values()) <= 2.0 * fair, counts


async def test_rebalance_exact_capacity_with_minimal_churn():
    """Flat-mode rebalance lands EXACT integer quotas at zero extra churn.

    After killing 2 of 20 nodes: every displaced object moves (they must),
    nothing else does (stay-put preference in the quota repair evicts
    movers first), and the survivors' loads match largest-remainder quotas
    exactly (111/112 for 2000 over 18).
    """
    import numpy as np

    n_nodes, n_objects = 20, 2000
    p = JaxObjectPlacement(mode="sinkhorn")
    for i in range(n_nodes):
        p.register_node(f"10.0.0.{i}:50")
    ids = [ObjectId("T", str(i)) for i in range(n_objects)]
    await p.assign_batch(ids)
    await p.rebalance()
    before = {str(i): await p.lookup(i) for i in ids}

    class M:
        def __init__(self, addr, active):
            self.address, self.active = addr, active

    p.sync_members([M(f"10.0.0.{i}:50", active=i >= 2) for i in range(n_nodes)])
    dead = {f"10.0.0.{j}:50" for j in range(2)}
    displaced = sum(1 for v in before.values() if v in dead)
    moved = await p.rebalance()
    assert moved == displaced, (moved, displaced)

    after = [await p.lookup(i) for i in ids]
    assert not any(a in dead for a in after)
    loads = np.bincount(
        [int(a.rsplit(":", 1)[0].rsplit(".", 1)[1]) for a in after],
        minlength=n_nodes,
    )
    live = loads[2:]
    assert int(live.max()) - int(live.min()) <= 1  # exact integer quotas


async def test_flat_rebalance_uses_collapsed_solve():
    """Flat modes collapse to the (M x M) class problem — N drops out.

    The class solve + move-minimal application must move EXACTLY the
    displaced share (zero off-diagonal churn at the sharpened class eps)
    and record the collapsed mode; solve time must not scale with N on
    the device (the N-sized work is one host pass + the quota repair).
    """
    import numpy as np

    m, n = 64, 20_000
    p = JaxObjectPlacement(mode="sinkhorn")
    for i in range(m):
        p.register_node(f"10.0.{i // 16}.{i % 16}:50")
    rng = np.random.default_rng(3)
    seats = rng.integers(0, m, n)
    for i, idx in enumerate(seats):
        p._set_placement(f"T.{i}", int(idx))
    p._recount_loads()

    class M:
        def __init__(self, addr, active):
            self.address, self.active = addr, active

    members = [
        M(f"10.0.{i // 16}.{i % 16}:50", active=i >= 6) for i in range(m)
    ]
    p.sync_members(members)
    displaced = int((seats < 6).sum())
    moved = await p.rebalance()
    assert p.stats.mode == "sinkhorn+collapsed"
    # Zero off-diagonal churn from the solve itself; per-row quota
    # rounding can drift columns by +-1 each, so the repair may move up
    # to ~M extra objects — bounded by the NODE count, never a fraction
    # of N (at 1M x 1024 measured extra was exactly 0).
    assert displaced <= moved <= displaced + m, (moved, displaced)
    loads = np.bincount(list(p._placements.values()), minlength=p._node_axis)
    assert loads[:6].sum() == 0
    live = loads[6:m]
    assert int(live.max()) - int(live.min()) <= 1


def test_apply_class_quotas_unit():
    """Quota expansion keeps quota[k,k] objects seated, spills the rest."""
    import numpy as np

    from rio_tpu.object_placement.jax_placement import _apply_class_quotas

    quotas = np.array(
        [
            [2, 1, 0],  # class 0: keep 2, send 1 to node 1
            [0, 3, 0],  # class 1: all stay
            [1, 0, 1],  # class 2: one to node 0, one stays
        ],
        np.int32,
    )
    cur = np.array([0, 0, 0, 1, 1, 1, 2, 2], np.int32)
    out = _apply_class_quotas(quotas, cur)
    assert np.bincount(out, minlength=3).tolist() == [3, 4, 1]
    # stay-put priority: exactly quota[k,k] of each class unchanged
    for k in range(3):
        stayed = int(((cur == k) & (out == k)).sum())
        assert stayed == quotas[k, k]


def test_expand_class_quotas_matches_host_apply():
    """Device quota expansion is byte-identical to the host expansion.

    The collapsed rebalance now runs expansion on device
    (``ops.structured.expand_class_quotas``); the host
    ``_apply_class_quotas`` stays as the semantic reference. Covers
    padding (bucket > n), empty classes, and skewed quota rows.
    """
    import jax.numpy as jnp
    import numpy as np

    from rio_tpu.object_placement.jax_placement import _apply_class_quotas
    from rio_tpu.ops.structured import expand_class_quotas

    rng = np.random.default_rng(7)
    for m, n in ((3, 8), (17, 900), (64, 4000)):
        cur = rng.integers(0, m, n).astype(np.int32)
        cur[: n // 5] = 0  # ensure class 0 is populated (padding shares it)
        counts = np.bincount(cur, minlength=m)
        quotas = np.zeros((m, m), np.int32)
        for k in range(m):
            if counts[k]:
                quotas[k] = rng.multinomial(counts[k], np.ones(m) / m)
        host = _apply_class_quotas(quotas, cur)
        bucket = 1
        while bucket < n:
            bucket *= 2
        cur_pad = np.zeros(bucket, np.int32)
        cur_pad[:n] = cur
        dev = np.asarray(
            expand_class_quotas(jnp.asarray(quotas), jnp.asarray(cur_pad))
        )[:n]
        assert (host == dev).all(), (m, n, np.nonzero(host != dev)[0][:5])


def test_provider_construction_initializes_no_backend():
    """Constructing a provider must NEVER initialize a jax backend.

    mode="auto" once resolved via jax.default_backend() in __init__;
    construction (e.g. inside a Server bootstrap, before
    multihost.initialize has run) must stay backend-free — the first
    SOLVE initializes the backend instead.
    """
    import subprocess
    import sys as _sys

    code = (
        "from rio_tpu.object_placement.jax_placement import JaxObjectPlacement\n"
        "p = JaxObjectPlacement()\n"
        "p.register_node('10.0.0.1:1')\n"
        "from jax._src import xla_bridge as xb\n"
        "assert not xb._backends, f'backend initialized: {list(xb._backends)}'\n"
        "print('CLEAN')\n"
    )
    proc = subprocess.run(
        [_sys.executable, "-c", code],
        capture_output=True,
        env={"PYTHONPATH": ".", "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd="/root/repo",
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr.decode()
    assert b"CLEAN" in proc.stdout


def test_guard_sentinel_spill_reseats_on_live_capacity():
    """fp32 largest-remainder drift can park a real object on the padding
    sentinel column (r4: bucket=2^24 == the fp32 integer boundary); the
    guard must reseat exactly those rows on the best live node and leave
    everything else untouched."""
    import jax.numpy as jnp

    from rio_tpu.object_placement.jax_placement import _guard_sentinel_spill

    m_axis = 4
    #             real rows --------------  padding
    repaired = jnp.asarray([0, m_axis, 2, 1, m_axis, m_axis], jnp.int32)
    real = jnp.asarray([True, True, True, True, False, False])
    cap_alive = jnp.asarray([1.0, 0.0, 2.0, 1.0], jnp.float32)  # node 1 dead
    out = _guard_sentinel_spill(repaired, real, m_axis, cap_alive)
    # Row 1 (real, spilled) reseats on node 2 (max live capacity); padding
    # rows keep the sentinel; everyone else is untouched.
    assert out.tolist() == [0, 2, 2, 1, m_axis, m_axis]


async def test_assign_batch_concurrent_with_membership_churn():
    """The chunked off-loop solve must tolerate loop-side membership
    mutations between/during chunks: sync_members and register_node run
    lock-free on the event loop while the solver thread reads only
    snapshots (r4 race fix). The batch spans multiple chunks; memberships
    flip while it runs; every object must land on a known node."""
    import asyncio

    placement = JaxObjectPlacement(mode="greedy")
    base = [f"10.1.0.{i}:70" for i in range(8)]
    placement.sync_members(base)

    churn_done = asyncio.Event()

    async def churner():
        extra = 8
        while not churn_done.is_set():
            # Flip a member out and in, and grow the node set (which can
            # double the node axis mid-batch).
            placement.sync_members(base[1:])
            await asyncio.sleep(0)
            placement.sync_members(base + [f"10.1.1.{extra}:70"])
            extra += 1
            await asyncio.sleep(0)

    # Shrink the chunk so the batch needs several solve round trips.
    old_chunk = JaxObjectPlacement._MAX_PLACE_CHUNK
    JaxObjectPlacement._MAX_PLACE_CHUNK = 1024
    try:
        task = asyncio.create_task(churner())
        ids = [ObjectId("Race", str(i)) for i in range(6000)]
        where = await placement.assign_batch(ids)
    finally:
        churn_done.set()
        await task
        JaxObjectPlacement._MAX_PLACE_CHUNK = old_chunk
    assert len(where) == len(ids)
    known = set(placement._node_order)
    assert all(w in known for w in where)
    # The directory answers for every object afterwards.
    looked = await placement.lookup_batch(ids)
    assert all(w is not None for w in looked)


async def test_assign_batch_releases_lock_between_chunks(monkeypatch):
    """r4 review: a huge batch must not hold the provider lock for its
    whole runtime. A locked mutator (remove of a chunk-0 key) queues on the
    lock WHILE chunk 0 is still held, so FIFO fairness serves it in the
    between-chunk gap — it must complete while the batch is still running
    (the old whole-batch hold blocked it until the end), and the batch's
    final resolution pass must re-place the removed straggler."""
    placement = JaxObjectPlacement(mode="greedy")
    placement.sync_members([f"10.5.0.{i}:70" for i in range(4)])
    ids = [ObjectId("Big", str(i)) for i in range(4000)]
    batch_done = False
    removed_while_batch_ran = None

    async def remove_a_chunk0_key(p):
        nonlocal removed_while_batch_ran
        await p.remove(ids[3])
        removed_while_batch_ran = not batch_done

    monkeypatch.setattr(JaxObjectPlacement, "_MAX_PLACE_CHUNK", 512)
    task = await _after_first_chunk(placement, remove_a_chunk0_key)
    where = await placement.assign_batch(ids)
    batch_done = True
    await asyncio.wait_for(task, 30)
    # The remove interleaved mid-batch (lock released between chunks)...
    assert removed_while_batch_ran is True
    # ...and the final resolution re-placed it: every key resolves.
    assert len(where) == len(ids)
    known = set(placement._node_order)
    assert all(w in known for w in where)
    looked = await placement.lookup_batch(ids)
    assert all(w is not None for w in looked)


async def test_cordon_drains_node_gracefully():
    """kubectl-cordon analog: a cordoned node takes no NEW seats, a
    rebalance re-seats exactly ~its population (not a global reshuffle),
    and uncordon makes it schedulable again."""
    import asyncio  # noqa: F401  (parity with sibling tests)

    p = JaxObjectPlacement(mode="greedy", move_cost=0.5)
    nodes = [f"10.6.0.{i}:70" for i in range(4)]
    p.sync_members(nodes)
    ids = [ObjectId("D", str(i)) for i in range(400)]
    await p.assign_batch(ids)
    victim = await p.lookup(ids[0])
    on_victim = sum(1 for w in await p.lookup_batch(ids) if w == victim)
    assert on_victim > 0

    p.cordon(victim)
    assert p.cordoned == {victim}
    # New allocations avoid it...
    where_new = await p.assign_batch([ObjectId("D", f"n{i}") for i in range(60)])
    assert victim not in where_new
    # ...its existing rows still resolve (it keeps serving)...
    assert await p.lookup(ids[0]) == victim
    # ...and a rebalance drains it, moving ~only its population.
    moved = await p.rebalance()
    where = await p.lookup_batch(ids)
    assert victim not in where
    assert moved <= on_victim + 460 // 3, (moved, on_victim)

    p.uncordon(victim)
    refill = await p.assign_batch([ObjectId("D", f"m{i}") for i in range(200)])
    assert victim in refill  # the drained node is schedulable (and emptiest)


async def test_cordon_refuses_last_schedulable_node():
    p = JaxObjectPlacement(mode="greedy")
    p.sync_members(["10.6.1.0:70", "10.6.1.1:70"])
    p.cordon("10.6.1.0:70")
    import pytest

    with pytest.raises(RuntimeError):
        p.cordon("10.6.1.1:70")
    with pytest.raises(KeyError):
        p.cordon("10.6.9.9:70")
    p.uncordon("10.6.1.0:70")
    assert p.cordoned == set()


async def test_hierarchical_rebalance_compiles_are_bucket_bounded():
    """r5 endurance regression: a steadily-allocating cluster must NOT
    compile a fresh hierarchical executable per rebalance (the jit cache
    retained ~25 MB per new directory size — ~1 GB/hour). The object axis
    is padded to power-of-two buckets, so rebalances at many different
    sizes within one bucket reuse ONE trace."""
    from rio_tpu.parallel.hierarchical import hierarchical_assign

    if not hasattr(hierarchical_assign, "_cache_size"):
        import pytest

        pytest.skip("jax jit cache probe (_cache_size) unavailable")
    p = JaxObjectPlacement(mode="hierarchical")
    p.sync_members([f"10.11.0.{i}:70" for i in range(3)])
    hierarchical_assign.clear_cache()
    n = 0
    for step in range(6):
        ids = [ObjectId("B", str(n + i)) for i in range(37)]  # 37: new n each step
        n += 37
        await p.assign_batch(ids)
        await p.rebalance(delta=False)  # pin the FULL path's compile bound
    # 6 different directory sizes, all inside the 256-bucket: one trace.
    assert hierarchical_assign._cache_size() == 1, hierarchical_assign._cache_size()
    # Crossing the bucket boundary adds exactly one more.
    ids = [ObjectId("B", str(n + i)) for i in range(120)]
    await p.assign_batch(ids)
    await p.rebalance(delta=False)
    assert hierarchical_assign._cache_size() == 2, hierarchical_assign._cache_size()


async def test_solve_stats_history_records_prior_solves():
    placement = JaxObjectPlacement(mode="greedy")
    placement.sync_members([f"10.2.0.{i}:80" for i in range(4)])
    ids = [ObjectId("Hist", str(i)) for i in range(200)]
    await placement.assign_batch(ids)
    await placement.rebalance()
    first_epoch = placement.stats.epoch
    assert placement.stats.history == []  # nothing completed before it
    await placement.rebalance()
    hist = placement.stats.history
    assert [h.epoch for h in hist] == [first_epoch]
    assert hist[0].history == []  # entries are flat, never nested
    assert placement.stats.epoch > first_epoch


async def test_hierarchical_rebalance_chunks_above_threshold(monkeypatch):
    """Above _HIER_CHUNK_ROWS the single-chip hierarchical solve must route
    through chunked_hierarchical_assign (TPU compile is superlinear in the
    flat row count; the chunked body compiles once at the chunk shape) and
    still produce a valid, balanced directory."""
    from rio_tpu.object_placement import jax_placement as jp_mod
    from rio_tpu.parallel import hierarchical as hier_mod

    monkeypatch.setattr(jp_mod, "_HIER_CHUNK_ROWS", 512)
    calls = {"n_chunks": None}
    # The placement routes through the timed host-loop twin by default
    # (RIO_TPU_CHUNK_TIMING=1) and the lax.map form when it's off; spy on
    # both so the test pins the routing, not the timing flavor.
    for name in ("chunked_hierarchical_assign", "chunked_hierarchical_assign_timed"):
        real = getattr(hier_mod, name)

        def spy(*args, _real=real, **kw):
            calls["n_chunks"] = kw.get("n_chunks")
            return _real(*args, **kw)

        monkeypatch.setattr(hier_mod, name, spy)

    p = JaxObjectPlacement(mode="hierarchical", n_iters=10)
    members = [f"10.31.0.{i}:70" for i in range(6)]
    p.sync_members(members)
    ids = [ObjectId("Chunky", str(i)) for i in range(1200)]  # bucket 2048 -> 4 chunks
    await p.assign_batch(ids)
    await p.rebalance()
    assert calls["n_chunks"] == 4
    # Directory still complete, every seat on a live member, loads balanced.
    addrs = [await p.lookup(i) for i in ids]
    assert all(a in members for a in addrs)
    from collections import Counter

    loads = Counter(addrs)
    assert max(loads.values()) <= 2.0 * (1200 / 6)


async def test_flat_rebalance_routes_to_hierarchical_at_scale(monkeypatch):
    """Flat OT modes above _FLAT_REBALANCE_MAX_ROWS must re-solve through
    the two-level pipeline (the flat collapsed expansion is
    compile-infeasible on the TPU backend at 10M-row shapes) and record
    what actually ran in SolveStats.mode."""
    from rio_tpu.object_placement import jax_placement as jp_mod

    monkeypatch.setattr(jp_mod, "_FLAT_REBALANCE_MAX_ROWS", 256)
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10)
    members = [f"10.32.0.{i}:70" for i in range(5)]
    p.sync_members(members)
    ids = [ObjectId("Big", str(i)) for i in range(700)]  # bucket 1024 > 256
    await p.assign_batch(ids)
    moved = await p.rebalance()
    assert p.stats.mode == "sinkhorn+hier_at_scale"
    assert moved >= 0
    addrs = [await p.lookup(i) for i in ids]
    assert all(a in members for a in addrs)
    from collections import Counter

    loads = Counter(addrs)
    assert max(loads.values()) <= 2.0 * (700 / 5)
    # Below the threshold the collapsed fast path still runs.
    monkeypatch.setattr(jp_mod, "_FLAT_REBALANCE_MAX_ROWS", 1 << 20)
    await p.rebalance(delta=False)
    assert p.stats.mode == "sinkhorn+collapsed"


async def test_routed_hier_rebalance_honors_move_cost(monkeypatch):
    """Review regression: a flat-mode rebalance routed through the
    hierarchical solve at scale must keep stay-put semantics. The pull of
    move_cost toward the current seat's embedding is the feature-space
    analog of the flat path's stay-put diagonal: re-solving an
    already-seated directory must move almost nothing (measured 12 vs 631
    unsticky), and a node death must move ~only the displaced share."""
    from rio_tpu.object_placement import jax_placement as jp_mod

    monkeypatch.setattr(jp_mod, "_FLAT_REBALANCE_MAX_ROWS", 256)
    members = [f"10.40.0.{i}:70" for i in range(8)]
    ids = [ObjectId("S", str(i)) for i in range(700)]

    async def settle_and_kill(move_cost):
        p = JaxObjectPlacement(mode="sinkhorn", n_iters=10, move_cost=move_cost)
        p.sync_members(members)
        await p.assign_batch(ids)
        settle = await p.rebalance()
        assert p.stats.mode == "sinkhorn+hier_at_scale"
        p.sync_members(members[:-1])
        after_kill = await p.rebalance()
        addrs = [await p.lookup(i) for i in ids]
        assert all(a in members[:-1] for a in addrs)  # dead node vacated
        return settle, after_kill

    settle_free, _ = await settle_and_kill(0.0)
    settle_sticky, after_kill = await settle_and_kill(1.0)
    displaced = 700 / 8
    # The absolute sticky count is jax-version sensitive (measured 12 on
    # jax>=0.6, 63 on 0.4.37 — Sinkhorn numerics shift the marginal group
    # boundaries); the contract is the RATIO: sticky must be a small
    # fraction of the population and far below the unsticky solve (~600).
    assert settle_sticky <= 100, settle_sticky           # measured 12-63
    assert settle_free >= 5 * settle_sticky + 100        # measured 609-631
    assert after_kill <= 2.0 * displaced, after_kill     # measured 90-93


async def test_mesh_flat_rebalance_routes_by_per_shard_rows(monkeypatch):
    """Review regression: the compile-feasibility guard keys on PER-SHARD
    rows — a mesh-sharded flat solve whose shards exceed the proven bound
    must route to the sharded hierarchical branch, and one whose shards
    fit must keep the dense sharded path."""
    from rio_tpu.object_placement import jax_placement as jp_mod
    from rio_tpu.parallel import make_mesh

    mesh = make_mesh()  # 8 virtual CPU devices (conftest)
    n_dev = int(mesh.devices.size)
    members = [f"10.41.0.{i}:70" for i in range(6)]
    ids = [ObjectId("MeshBig", str(i)) for i in range(700)]  # bucket 1024

    async def run(threshold):
        p = JaxObjectPlacement(mode="sinkhorn", n_iters=10, mesh=mesh)
        p.sync_members(members)
        await p.assign_batch(ids)
        await p.rebalance()
        addrs = [await p.lookup(i) for i in ids]
        assert all(a in members for a in addrs)
        return p.stats.mode

    # bucket/n_dev = 128 per shard: > 64 routes, > 1024 keeps dense.
    monkeypatch.setattr(jp_mod, "_FLAT_REBALANCE_MAX_ROWS", 64)
    assert await run(64) == "sinkhorn+hier_at_scale"
    monkeypatch.setattr(jp_mod, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    assert await run(1024) == "sinkhorn"


async def test_assign_with_every_node_dead_still_seats_on_real_nodes():
    """A clean_server storm can mark EVERY node dead between gossip ticks
    (80-wave soak, wave 46): the waterfill's width vector collapses and,
    unguarded, searchsorted clipped rows onto padded-axis slots — a pad
    index in the directory then blew up every _node_order[idx] resolution
    (IndexError in the persistence mark was the observed symptom). The
    directory must still seat such objects on REAL nodes (reference
    semantics: placement rows outlive their owner, service.rs:213-238);
    the next liveness change re-seats them."""
    p = JaxObjectPlacement(mode="greedy", move_cost=0.5)
    members = [f"10.9.0.{i}:70" for i in range(6)]
    p.sync_members(members)
    ids = [ObjectId("Dead", str(i)) for i in range(40)]
    await p.assign_batch(ids[:10])
    for a in members:
        await p.clean_server(a)  # every node now dead, loads zeroed
    addrs = await p.assign_batch(ids[10:])
    assert all(a in members for a in addrs)
    # Spread, not a single-node pileup: least-loaded round-robin.
    assert len(set(addrs)) == len(members)
    # Rebalance with the all-dead snapshot must not corrupt the directory
    # either (same funnel guard, every solver mode).
    await p.rebalance()
    for i in ids[10:]:
        assert await p.lookup(i) in members
    # Recovery: liveness returns -> the next rebalance re-seats cleanly.
    p.sync_members(members)
    await p.rebalance()
    for i in ids[10:]:
        assert await p.lookup(i) in members
    _ = p.count()


async def test_gossip_blip_marking_all_nodes_dead_spreads_and_stays_put():
    """The sync_members variant of the all-dead case (loads retained, no
    clean_server): the unguarded waterfill piled the whole batch onto ONE
    worst-scored node here — the condition-level guard must spread the
    batch least-loaded round-robin, and a rebalance under zero capacity
    must STAY PUT (reshuffling among dead nodes is pure churn) and say so
    in its stats mode."""
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=8, move_cost=0.5)
    members = [f"10.9.1.{i}:70" for i in range(6)]
    p.sync_members(members)
    ids = [ObjectId("Blip", str(i)) for i in range(36)]
    await p.assign_batch(ids[:12])
    before = {str(i): await p.lookup(i) for i in ids[:12]}

    class _Dead:
        def __init__(self, a):
            self._a = a
            self.active = False
        def address(self):
            return self._a

    p.sync_members([_Dead(a) for a in members])  # every node inactive
    addrs = await p.assign_batch(ids[12:])
    assert all(a in members for a in addrs)
    assert len(set(addrs)) == len(members)  # spread, not a pileup
    moved = await p.rebalance()
    assert moved == 0
    assert p.stats.mode.endswith("+no_capacity")
    for i in ids[:12]:  # pre-blip seats untouched
        assert await p.lookup(i) == before[str(i)]
    # Liveness returns: the next rebalance runs the real solver again.
    p.sync_members(members)
    await p.rebalance()
    assert not p.stats.mode.endswith("+no_capacity")
    for i in ids:
        assert await p.lookup(i) in members


def test_least_loaded_spread_prefers_schedulable_prefix():
    """Overflow seats cycle ONLY schedulable (alive AND capacity>0) nodes
    while any exist (cordon's no-new-seats contract, and the operator's
    capacity=0 don't-place-here signal); dead nodes' zeroed loads must not
    rank them first."""
    from rio_tpu.object_placement.jax_placement import _least_loaded_spread

    load = np.array([5, 0, 3, 1], np.float32)  # node 1 dead, load zeroed
    alive = np.array([1, 0, 1, 1], np.float32)
    cap = np.ones(4, np.float32)
    out = _least_loaded_spread(load, alive, cap, 4, 7)
    assert 1 not in out.tolist()
    assert out[0] == 3  # least-loaded schedulable node first
    # A lone alive node with capacity=0 must NOT absorb the whole batch
    # while other schedulable nodes exist.
    cap0 = np.array([1, 1, 1, 0], np.float32)
    out = _least_loaded_spread(load, alive, cap0, 4, 7)
    assert 3 not in out.tolist() and 1 not in out.tolist()
    # All-dead: every real node cycles (any seat beats a pad index).
    out = _least_loaded_spread(load, np.zeros(4, np.float32), cap, 4, 8)
    assert sorted(set(out.tolist())) == [0, 1, 2, 3]
    # All-dead-or-capacity-zero: still spreads over every real node
    # rather than piling onto the lone alive capacity-zero node.
    alive_only3 = np.array([0, 0, 0, 1], np.float32)
    out = _least_loaded_spread(load, alive_only3, cap0, 4, 8)
    assert sorted(set(out.tolist())) == [0, 1, 2, 3]


async def test_hierarchical_solve_sanitizes_nonfinite_features(monkeypatch):
    """ISSUE 18 satellite: garbage feature rows (a NaN/inf-emitting custom
    hook) must not poison the solve. One NaN row would propagate through
    the coarse cost's std normalization into EVERY object's cost; the
    streamed obj_feat builder zeroes non-finite entries instead, so the
    directory stays complete, on live members, and balanced."""
    import numpy as np

    from rio_tpu.object_placement.jax_placement import _hash_features

    def poisoned(keys):
        feats = np.asarray(_hash_features(keys), np.float32).copy()
        for i, k in enumerate(keys):
            if k.endswith("3"):
                feats[i, 0] = np.nan
                feats[i, 1] = np.inf
            elif k.endswith("7"):
                feats[i] = -np.inf
        return feats

    p = JaxObjectPlacement(
        mode="hierarchical", n_iters=10, obj_features=poisoned
    )
    members = [f"10.33.0.{i}:70" for i in range(8)]
    p.sync_members(members)
    ids = [ObjectId("Nan", str(i)) for i in range(640)]
    await p.assign_batch(ids)
    await p.rebalance()
    addrs = [await p.lookup(i) for i in ids]
    assert all(a in members for a in addrs)
    from collections import Counter

    loads = Counter(addrs)
    assert max(loads.values()) <= 2.0 * (640 / 8)
    # The solve itself converged on finite numbers.
    assert np.isfinite(p.stats.residual) or p.stats.residual == -1.0


async def test_hierarchical_bf16_feature_knob(monkeypatch):
    """RIO_TPU_HIER_FEAT_BF16=1 stores the streamed feature block in
    bfloat16 (half the host bytes at 10M rows); the solve upcasts on
    device and the directory contract is unchanged."""
    monkeypatch.setenv("RIO_TPU_HIER_FEAT_BF16", "1")
    p = JaxObjectPlacement(mode="hierarchical", n_iters=10)
    members = [f"10.34.0.{i}:70" for i in range(8)]
    p.sync_members(members)
    ids = [ObjectId("Bf", str(i)) for i in range(640)]
    await p.assign_batch(ids)
    await p.rebalance()
    addrs = [await p.lookup(i) for i in ids]
    assert all(a in members for a in addrs)
    from collections import Counter

    loads = Counter(addrs)
    assert max(loads.values()) <= 2.0 * (640 / 8)


async def test_flat_rebalance_at_scale_composes_with_mesh(monkeypatch):
    """ISSUE 18 tentpole routing: the _FLAT_REBALANCE_MAX_ROWS guard used
    to refuse giant flat solves; on a mesh it now lands on the composed
    mesh x chunk dispatch (chunks AND devices both bound the compiled
    shape) and says so in SolveStats."""
    from rio_tpu.object_placement import jax_placement as jp_mod
    from rio_tpu.parallel import make_mesh

    monkeypatch.setattr(jp_mod, "_FLAT_REBALANCE_MAX_ROWS", 256)
    monkeypatch.setattr(jp_mod, "_HIER_CHUNK_ROWS", 64)
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10, mesh=make_mesh())
    members = [f"10.35.0.{i}:70" for i in range(6)]
    p.sync_members(members)
    ids = [ObjectId("BigMesh", str(i)) for i in range(3000)]
    await p.assign_batch(ids)
    moved = await p.rebalance()
    assert p.stats.mode == "sinkhorn+hier_at_scale+mesh_chunk"
    assert p.stats.devices == 8
    assert p.stats.chunks > 1
    assert moved >= 0
    addrs = [await p.lookup(i) for i in ids]
    assert all(a in members for a in addrs)


# ---------------------------------------------------------------------------
# The host mirror: nothing per key where the collector walks, one bulk seam
# ---------------------------------------------------------------------------


def _tracked_entries(p) -> int:
    """Entries of every collector-tracked container reachable from
    ``vars(p)`` through containers and rio_tpu's own objects."""
    seen, total, todo = set(), 0, list(vars(p).values())
    while todo:
        o = todo.pop()
        if id(o) in seen:
            continue
        seen.add(id(o))
        if isinstance(o, (dict, list, tuple, set, frozenset)):
            if gc.is_tracked(o):
                total += len(o)
            todo.extend(o.values() if isinstance(o, dict) else o)
        elif type(o).__module__.startswith("rio_tpu") and hasattr(o, "__dict__"):
            todo.extend(vars(o).values())
    return total


async def _moves_by_rebalance(p, ids):
    p.sync_members([f"10.0.0.{i}:5000" for i in range(2, 8)])
    assert await p.rebalance() > 0


async def _remove_and_clean_server(p, ids):
    for oid in ids[:50]:
        await p.remove(oid)
    await p.clean_server("10.0.0.3:5000")
    await p.assign_batch(ids[:25])  # a second bulk chunk into the holes


async def _update_and_promote(p, ids):
    await p.update(ObjectPlacementItem(ids[0], "10.0.0.5:5000"))
    await p.update(ObjectPlacementItem(ids[0], "10.0.0.6:5000"))
    epoch = await p.set_standbys(ids[1], ["10.0.0.7:5000"])
    assert await p.promote_standby(ids[1], "10.0.0.7:5000", epoch) == epoch + 1


async def _nothing_more(p, ids):
    pass


@pytest.mark.parametrize(
    "then, bulk_rows, bulk_chunks",
    [
        (_nothing_more, 4096, 1),
        (_moves_by_rebalance, 4096, 1),
        (_remove_and_clean_server, 4096 + 25, 2),
        (_update_and_promote, 4096, 1),
    ],
    ids=["bulk_assign", "rebalance_moves", "remove_clean_server", "update_promote"],
)
async def test_mirror_keeps_no_per_key_entry_where_the_collector_walks(
    then, bulk_rows, bulk_chunks
):
    """The contract of the per-node index: after any sequence of writers the
    only collector-tracked containers the provider holds are O(nodes) long.
    Never skipped: an interpreter that tracks every dict fails it, and the
    index then has to become arrays (``_by_node``'s comment)."""
    from types import SimpleNamespace

    from rio_tpu.otel import server_gauges

    nodes = 8
    p = _provider(nodes=nodes, mode="greedy")
    ids = [ObjectId("Presence", str(i)) for i in range(4096)]
    await p.assign_batch(ids)
    await then(p, ids)
    _check_invariants(p)
    assert not gc.is_tracked(p._placements)
    assert p._by_node and not any(gc.is_tracked(c) for c in p._by_node.values())
    # The node table, the outer index, the stats' history: nothing of N.
    assert _tracked_entries(p) <= 16 * nodes, _tracked_entries(p)
    gauges = server_gauges(SimpleNamespace(object_placement=p))
    assert gauges["rio.place.index_tracked_rows"] == 0
    assert gauges["rio.place.bulk_rows"] == bulk_rows
    assert gauges["rio.place.bulk_chunks"] == bulk_chunks
    # The gauge is the alarm: a container the collector walks reads as rows.
    j = max(p._by_node, key=lambda j: len(p._by_node[j]))
    p._by_node[j] = set(p._by_node[j])
    assert p.place_gauges()["rio.place.index_tracked_rows"] == len(p._by_node[j]) > 0


def _seat_per_key(p, keys, assignment):
    """``_apply_chunk`` as it was: the per-key seam, one call a key."""
    for k, idx in zip(keys, assignment.tolist()):
        p._set_placement(k, int(idx))
        p._nodes[p._node_order[idx]].load += 1.0
    p._epoch += 1


@pytest.mark.parametrize("n", [0, 1, 160, 5000])
async def test_bulk_seam_seats_what_the_per_key_seam_seats(n):
    nodes = 8
    bulk, per_key = _provider(nodes=nodes), _provider(nodes=nodes)
    rng = np.random.default_rng(n)
    seated = [f"T.old{i}" for i in range(300)]
    keys = [f"T.{i}" for i in range(n)]
    for p in (bulk, per_key):  # a mirror that already holds rows
        _seat_per_key(p, seated, np.arange(300, dtype=np.int32) % nodes)
    assignment = rng.integers(0, nodes - 1, n).astype(np.int32)  # node 7 takes none
    bulk._apply_chunk(keys, assignment)
    _seat_per_key(per_key, keys, assignment)
    assert list(bulk._placements.items()) == list(per_key._placements.items())
    assert {j: list(c) for j, c in bulk._by_node.items()} == {
        j: list(c) for j, c in per_key._by_node.items()
    }
    assert [s.load for s in bulk._nodes.values()] == [s.load for s in per_key._nodes.values()]
    assert bulk._epoch == per_key._epoch
    _check_invariants(bulk)
    assert bulk.place_gauges()["rio.place.bulk_rows"] == n


async def test_a_key_given_twice_in_a_batch_is_solved_and_counted_once():
    """It was solved twice, moved by its second row, and its first node
    kept a load of +1 it did not hold."""
    p = _provider(nodes=4, mode="greedy")
    names = [str(i % 100) for i in range(250)]  # every id 2 or 3 times
    ids = [ObjectId("Game", n) for n in names]
    addrs = await p.assign_batch(ids)
    assert len(addrs) == 250 and p.count() == 100
    assert len({(n, a) for n, a in zip(names, addrs)}) == 100
    assert addrs == await p.lookup_batch(ids)
    _check_invariants(p)
    loads = {s.index: s.load for s in p._nodes.values()}
    assert loads == {j: float(len(p._by_node.get(j, ()))) for j in loads}
    assert sum(loads.values()) == 100.0
    assert p.place_gauges()["rio.place.bulk_rows"] == 100


# ---------------------------------------------------------------------------
# assign_batch answers from the seats it carried; the epoch says when it may not
# ---------------------------------------------------------------------------


def _directory(kind: str, nodes: int = 6):
    if kind == "persistent":
        p = PersistentJaxObjectPlacement(
            LocalObjectPlacement(), flush_interval=0.01, node_axis_size=16, mode="greedy"
        )
    else:
        p = JaxObjectPlacement(node_axis_size=16, mode="greedy")
    for i in range(nodes):
        p.register_node(f"10.0.0.{i}:5000")
    return p


def _game_ids(lo, hi):
    return [ObjectId("Game", str(i)) for i in range(lo, hi)]


async def _after_first_chunk(p, action):
    """Run ``action(p)`` between the first chunk's lock hold and the second's:
    the task asks for the lock while chunk 0 still holds it, and the lock is
    first come, first served."""
    chunk0_done = asyncio.Event()
    real = p._place_chunk_locked

    async def chunk_and_signal(chunk):
        seats = await real(chunk)
        if not chunk0_done.is_set():
            chunk0_done.set()
            for _ in range(5):
                await asyncio.sleep(0)
        return seats

    p._place_chunk_locked = chunk_and_signal

    async def mutator():
        await chunk0_done.wait()
        await action(p)

    return asyncio.create_task(mutator())


async def _remove_a_chunk0_key(p):
    await p.remove(ObjectId("Game", "3"))


async def _clean_a_server(p):
    await p.clean_server("10.0.0.2:5000")


async def _update_a_chunk0_key(p):
    oid = ObjectId("Game", "5")
    held = await p.lookup(oid)
    await p.update(ObjectPlacementItem(oid, next(a for a in p._node_order if a != held)))


async def _drop_a_chunk0_key_by_update(p):
    await p.update(ObjectPlacementItem(ObjectId("Game", "7"), None))


async def _reprice_every_node(p):
    async with p._lock:  # in the gap between two holds, like the others
        p.sync_load(SimpleNamespace(derate=lambda address: 0.5))


# name: (ids seated before, the batch, chunk size, what lands between chunks, carried?)
_ASSIGN_CASES = {
    "all_new": ([], _game_ids(0, 300), None, None, True),
    "some_seated": (_game_ids(100, 200), _game_ids(50, 250)[::-1], None, None, True),
    "all_seated": (_game_ids(0, 300), _game_ids(0, 300), None, None, True),
    "a_key_twice": ([], _game_ids(0, 120) + _game_ids(60, 180), None, None, True),
    "several_chunks": (_game_ids(30, 90), _game_ids(0, 300) + _game_ids(10, 20), 64, None, True),
    "remove_between_chunks": ([], _game_ids(0, 300), 64, _remove_a_chunk0_key, False),
    "clean_server_between_chunks": ([], _game_ids(0, 300), 64, _clean_a_server, False),
    "update_between_chunks": ([], _game_ids(0, 300), 64, _update_a_chunk0_key, False),
    "update_to_none_between_chunks": (
        [], _game_ids(0, 300), 64, _drop_a_chunk0_key_by_update, False,
    ),
    "sync_load_between_chunks": ([], _game_ids(0, 300), 64, _reprice_every_node, True),
}


@pytest.mark.parametrize("kind", ["mirror", "persistent"])
@pytest.mark.parametrize("case", list(_ASSIGN_CASES))
async def test_assign_batch_returns_the_seat_the_directory_holds(case, kind, monkeypatch):
    """Whichever route the call takes, what it returns is what a probe of
    every key would read at the moment of return, and it took the carried
    route exactly when nobody else wrote seats or liveness between its holds."""
    seated, ids, chunk_size, between, carried = _ASSIGN_CASES[case]
    p = _directory(kind)
    if seated:
        await p.assign_batch(seated)
    if chunk_size:
        monkeypatch.setattr(JaxObjectPlacement, "_MAX_PLACE_CHUNK", chunk_size)
    before = p.place_gauges()
    task = await _after_first_chunk(p, between) if between else None
    addrs = await p.assign_batch(ids)
    if task is not None:
        await asyncio.wait_for(task, 30)
    assert addrs == [p._node_order[p._placements[str(o)]] for o in ids]
    assert addrs == await p.lookup_batch(ids) == [await p.lookup(o) for o in ids]
    assert p.count() == len({str(o) for o in ids} | {str(o) for o in seated})
    _check_invariants(p)
    if between is None:  # (remove and update leave loads to the next solve's recount)
        loads = {s.index: s.load for s in p._nodes.values()}
        assert loads == {j: float(len(p._by_node.get(j, ()))) for j in loads}
    if between is _clean_a_server:
        assert "10.0.0.2:5000" not in addrs and not p._by_node.get(2)
    after = p.place_gauges()
    took = {
        k: after[f"rio.place.assign_{k}"] - before[f"rio.place.assign_{k}"]
        for k in ("carried", "revalidated")
    }
    assert took == {"carried": float(carried), "revalidated": float(not carried)}
    if kind == "persistent":
        await p.flush()
        stored = {str(i.object_id): i.server_address for i in await p._backing.items()}
        assert all(stored[str(o)] == a for o, a in zip(ids, addrs))
        assert len(stored) == p.count()
        await p.aclose()


async def test_two_overlapping_batches_at_once_agree_on_every_shared_key(monkeypatch):
    """Each caller's chunks interleave with the other's, whose applies move
    the epoch: a seat carried over such a gap is re-validated, not trusted."""
    monkeypatch.setattr(JaxObjectPlacement, "_MAX_PLACE_CHUNK", 64)
    p = _directory("mirror")
    a, b = _game_ids(0, 400), _game_ids(200, 600)[::-1]
    got_a, got_b = await asyncio.wait_for(
        asyncio.gather(p.assign_batch(a), p.assign_batch(b)), 60
    )
    assert got_a == await p.lookup_batch(a) and got_b == await p.lookup_batch(b)
    assert p.count() == 600
    _check_invariants(p)
    gauges = p.place_gauges()
    assert gauges["rio.place.assign_carried"] + gauges["rio.place.assign_revalidated"] == 2
    assert gauges["rio.place.assign_revalidated"] >= 1
    assert gauges["rio.place.bulk_rows"] == 600  # no key was seated twice


@pytest.mark.parametrize("kind", ["mirror", "persistent"])
async def test_lookup_batch_reads_what_lookup_reads_and_none_for_an_unseated_id(kind):
    p = _directory(kind)
    await p.assign_batch(_game_ids(0, 50))
    ids = _game_ids(40, 60) + [ObjectId("Other", "7"), ObjectId("Game", "0")]
    got = await p.lookup_batch(ids)
    assert got == [await p.lookup(o) for o in ids]
    assert got[:10].count(None) == 0 and got[10:21] == [None] * 11 and got[21] is not None
    assert await p.lookup_batch([]) == [] and await p.assign_batch([]) == []
    if kind == "persistent":
        await p.aclose()


# ---------------------------------------------------------------------------
# The epoch contract the carried route rests on
# ---------------------------------------------------------------------------


class _AuditedLock(asyncio.Lock):
    """``p._lock`` that, at every release, holds the writer to the contract:
    a hold in which a seat was written ends with ``_epoch`` moved."""

    def __init__(self, p):
        super().__init__()
        self.p, self.wrote, self.epoch, self.broken, self.writes = p, [], None, [], 0

    def note(self, seam: str) -> None:
        assert self.locked(), f"{seam} wrote a seat without the lock"
        if not self.wrote:
            self.epoch = self.p._epoch
        self.wrote.append(seam)
        self.writes += 1

    def release(self) -> None:
        if self.wrote and self.p._epoch == self.epoch:
            self.broken.append(sorted(set(self.wrote)))
        self.wrote = []
        super().release()


def _audit_the_write_seams(p) -> _AuditedLock:
    lock = p._lock = _AuditedLock(p)
    set_, drop, bulk = p._set_placement, p._drop_placement, p._seat_new

    def set_placement(key, idx):
        changed = set_(key, idx)
        if changed:
            lock.note("_set_placement")
        return changed

    def drop_placement(key):
        idx = drop(key)
        if idx is not None:
            lock.note("_drop_placement")
        return idx

    def seat_new(keys, idx):
        if len(keys):
            lock.note("_seat_new")
        return bulk(keys, idx)

    p._set_placement, p._drop_placement, p._seat_new = set_placement, drop_placement, seat_new
    return lock


async def _epoch_update(p, ids):
    await p.update(ObjectPlacementItem(ids[0], "10.0.0.5:5000"))


async def _epoch_update_to_none(p, ids):
    await p.update(ObjectPlacementItem(ids[1], None))


async def _epoch_remove(p, ids):
    await p.remove(ids[2])


async def _epoch_clean_server(p, ids):
    await p.clean_server("10.0.0.1:5000")


async def _epoch_promote_standby(p, ids):
    held = await p.lookup(ids[3])
    other = next(a for a in p._node_order if a != held)
    epoch = await p.set_standbys(ids[3], [other])
    assert await p.promote_standby(ids[3], other, epoch) == epoch + 1


async def _epoch_rebalance_full(p, ids):
    p.sync_members([f"10.0.0.{i}:5000" for i in range(2, 9)])
    assert await p.rebalance(delta=False) > 0 and "+delta" not in p.stats.mode


async def _epoch_rebalance_delta(p, ids):
    await p.rebalance(delta=False)  # a committed plan to delta against
    p.sync_members([f"10.0.0.{i}:5000" for i in range(1, 6)])
    assert await p.rebalance() > 0 and p.stats.mode.endswith("+delta")


async def _epoch_assign_batch(p, ids):
    await p.assign_batch(_game_ids(1000, 1300))


async def _epoch_assign_batch_after_a_remove(p, ids):
    task = await _after_first_chunk(p, _remove_a_chunk0_key)
    await p.assign_batch(_game_ids(0, 200) + _game_ids(1000, 1300))
    await asyncio.wait_for(task, 30)


_EPOCH_MUTATORS = [
    _epoch_update, _epoch_update_to_none, _epoch_remove, _epoch_clean_server,
    _epoch_promote_standby, _epoch_rebalance_full, _epoch_rebalance_delta,
    _epoch_assign_batch, _epoch_assign_batch_after_a_remove,
]


@pytest.mark.parametrize("kind", ["mirror", "persistent"])
@pytest.mark.parametrize("mutator", _EPOCH_MUTATORS, ids=lambda f: f.__name__[7:])
async def test_every_writer_of_seats_moves_the_epoch_in_the_hold_it_wrote_in(
    mutator, kind, monkeypatch
):
    """``assign_batch`` trusts its carried seats while ``_epoch`` stands still
    between its lock holds. That is sound only while every write through
    ``_set_placement`` / ``_drop_placement`` / ``_seat_new`` happens under
    ``_lock`` and the hold it happens in ends with the epoch moved. A later
    writer that forgets the bump fails here, by seam, under its mutator's name."""
    monkeypatch.setattr(JaxObjectPlacement, "_MAX_PLACE_CHUNK", 128)
    p = _directory(kind)
    ids = _game_ids(0, 200)
    await p.assign_batch(ids)
    lock = _audit_the_write_seams(p)
    epoch = p._epoch
    await mutator(p, ids)
    assert lock.writes > 0, "the scenario wrote no seat: it proves nothing"
    assert lock.broken == [] and not lock.locked()
    assert p._epoch > epoch
    _check_invariants(p)
    if kind == "persistent":
        await p.aclose()


async def test_a_restore_with_items_moves_the_epoch_and_an_empty_one_need_not():
    backing = LocalObjectPlacement()
    empty = PersistentJaxObjectPlacement(backing, mode="greedy")
    lock = _audit_the_write_seams(empty)
    await empty.prepare()
    assert lock.writes == 0 and lock.broken == [] and empty._epoch == 0
    await backing.update(ObjectPlacementItem(ObjectId("Game", "1"), "10.0.0.1:5000"))
    await backing.update(ObjectPlacementItem(ObjectId("Game", "2"), "10.0.0.2:5000"))
    p = PersistentJaxObjectPlacement(backing, mode="greedy")
    lock = _audit_the_write_seams(p)
    await p.prepare()
    assert lock.writes == 2 and lock.broken == [] and p._epoch > 0
    assert await p.lookup_batch(_game_ids(1, 3)) == ["10.0.0.1:5000", "10.0.0.2:5000"]
    await empty.aclose()
    await p.aclose()
