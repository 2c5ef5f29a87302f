"""Load telemetry, admission control, and affinity-priced placement.

Covers the rio_tpu.load subsystem end to end: vector codec + chaos
sanitization, the heartbeat-piggybacked ClusterLoadView, capacity derating
in the solver, per-object move pricing (hot/heavy actors priced differently
from cold ones), and the ServerBusy shed/retry loop over real sockets.
"""

import asyncio
import math
import time
import types

import numpy as np

from rio_tpu import (
    AppData,
    ClusterLoadView,
    LoadMonitor,
    LoadThresholds,
    LoadVector,
    ObjectId,
    ObjectPlacementItem,
    Registry,
    ServerInfo,
    ServiceObject,
    handler,
    message,
)
from rio_tpu.cluster.storage import Member
from rio_tpu.load import (
    DEFAULT_MAX_STALENESS,
    MIN_DERATE,
    SUSTAIN_MISSES,
    SUSTAIN_TICKS,
    _StallWatchdog,
    capacity_derate,
)
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

from .server_utils import Cluster, run_integration_test


# ---------------------------------------------------------------------------
# LoadVector codec
# ---------------------------------------------------------------------------


def test_load_vector_roundtrip():
    v = LoadVector(
        loop_lag_ms=3.5, inflight=2, registry_objects=10,
        req_rate=1.25, state_bytes=4096, epoch=1700000000.0,
    )
    enc = v.encode()
    assert "," in enc and ";" not in enc  # must survive the Redis ';' join
    d = LoadVector.decode(enc)
    assert d is not None
    assert (d.loop_lag_ms, d.inflight, d.req_rate) == (3.5, 2, 1.25)


def test_load_vector_decode_tolerates_garbage():
    for raw in (None, "", "legacy", "1,2,3", "a,b,c,d,e,f"):
        assert LoadVector.decode(raw) is None
    # Parseable but insane values decode, then sanitize to something safe.
    v = LoadVector.decode("nan,-5,1e99,inf,-1,0")
    assert v is not None
    s = v.sanitized()
    assert s.loop_lag_ms == 0.0  # NaN -> default
    assert s.inflight == 0.0  # negative -> clamped
    assert math.isfinite(s.registry_objects)
    assert s.req_rate == 0.0  # inf -> default


def test_load_vector_sheds_append_only_growth():
    # Current 7-field rows round-trip the sheds counter...
    v = LoadVector(inflight=2, sheds=17.0, epoch=1700000000.0)
    d = LoadVector.decode(v.encode())
    assert d is not None and d.sheds == 17.0
    # ...pre-sheds 6-field legacy rows still decode (sheds defaults 0)...
    legacy = ",".join(v.encode().split(",")[:6])
    d6 = LoadVector.decode(legacy)
    assert d6 is not None and d6.sheds == 0.0 and d6.inflight == 2.0
    # ...and extra trailing fields from a NEWER sender are ignored.
    d8 = LoadVector.decode(v.encode() + ",99")
    assert d8 is not None and d8.sheds == 17.0


def test_capacity_derate_monotone_and_bounded():
    idle = capacity_derate(LoadVector())
    assert idle == 1.0
    assert capacity_derate(None) == 1.0
    hot = capacity_derate(LoadVector(loop_lag_ms=200.0, inflight=512))
    assert MIN_DERATE <= hot < idle
    # No input, however corrupt, escapes [MIN_DERATE, 1.0].
    for v in (
        LoadVector(loop_lag_ms=float("nan")),
        LoadVector(inflight=float("inf")),
        LoadVector(loop_lag_ms=-1e30, inflight=-5),
        LoadVector(loop_lag_ms=1e30, inflight=1e30),
    ):
        d = capacity_derate(v)
        assert MIN_DERATE <= d <= 1.0


# ---------------------------------------------------------------------------
# ClusterLoadView: staleness + chaos clamping
# ---------------------------------------------------------------------------


def _member(addr: str, load: str) -> Member:
    return Member.from_address(addr, active=True, load=load)


def test_cluster_view_staleness_and_garbage():
    now = time.time()
    fresh = LoadVector(inflight=512, epoch=now - 1.0).encode()
    old = LoadVector(inflight=512, epoch=now - 10 * DEFAULT_MAX_STALENESS).encode()
    zero_epoch = LoadVector(inflight=512, epoch=0.0).encode()
    future = LoadVector(inflight=512, epoch=now + 3600.0).encode()
    view = ClusterLoadView.from_members(
        [
            _member("10.0.0.1:1", fresh),
            _member("10.0.0.2:1", old),
            _member("10.0.0.3:1", zero_epoch),
            _member("10.0.0.4:1", future),
            _member("10.0.0.5:1", "total garbage"),
            _member("10.0.0.6:1", ""),  # legacy row: no vector at all
        ],
        now=now,
    )
    # Fresh + loaded: derated below 1.
    assert view.derate("10.0.0.1:1") < 1.0
    # Epoch-old: treated as unreported (derate 1.0), flagged stale.
    assert view.get("10.0.0.2:1").stale
    assert view.derate("10.0.0.2:1") == 1.0
    # Zero/future epochs are garbage -> infinitely stale, never "fresh".
    for addr in ("10.0.0.3:1", "10.0.0.4:1"):
        assert math.isinf(view.get(addr).staleness)
        assert view.derate(addr) == 1.0
    # Unparseable + legacy rows simply have no entry; unknown -> 1.0.
    assert view.get("10.0.0.5:1") is None
    assert view.derate("10.0.0.6:1") == 1.0
    assert view.derate("10.9.9.9:1") == 1.0
    # Gauges are flat floats; infinite staleness exports as -1.
    g = view.gauges()
    assert g["rio.cluster_load.10.0.0.1:1.inflight"] == 512.0
    assert g["rio.cluster_load.10.0.0.3:1.staleness"] == -1.0
    assert all(isinstance(x, float) and not math.isnan(x) for x in g.values())


def test_cluster_aggregate_gauges_roll_up_fresh_entries_only():
    now = time.time()
    a = LoadVector(loop_lag_ms=2.0, inflight=10, req_rate=100.0,
                   registry_objects=5, sheds=3.0, epoch=now - 1.0).encode()
    b = LoadVector(loop_lag_ms=6.0, inflight=30, req_rate=300.0,
                   registry_objects=15, sheds=4.0, epoch=now - 2.0).encode()
    stale = LoadVector(loop_lag_ms=999.0, inflight=999, req_rate=9999.0,
                       epoch=now - 10 * DEFAULT_MAX_STALENESS).encode()
    view = ClusterLoadView.from_members(
        [_member("10.0.0.1:1", a), _member("10.0.0.2:1", b),
         _member("10.0.0.3:1", stale)],
        now=now,
    )
    g = view.aggregate_gauges()
    assert g["rio.cluster.nodes"] == 2.0
    assert g["rio.cluster.nodes_stale"] == 1.0
    # The stale node's insane vector is excluded from every rollup.
    assert g["rio.cluster.loop_lag_mean_ms"] == 4.0
    assert g["rio.cluster.loop_lag_max_ms"] == 6.0
    assert g["rio.cluster.inflight_total"] == 40.0
    assert g["rio.cluster.req_rate_total"] == 400.0
    assert g["rio.cluster.registry_objects_total"] == 20.0
    assert g["rio.cluster.sheds_total"] == 7.0
    # The rollups ride the ordinary gauge scrape (fnmatch-selectable).
    assert view.gauges()["rio.cluster.req_rate_total"] == 400.0


def test_cluster_aggregate_gauges_empty_view_is_all_zero():
    view = ClusterLoadView.from_members([], now=time.time())
    g = view.aggregate_gauges()
    assert g["rio.cluster.nodes"] == 0.0
    assert all(v == 0.0 for v in g.values())


def test_cluster_view_chaos_vectors_all_bounded():
    """A cluster full of adversarial heartbeat rows produces only bounded
    derates — nothing a peer publishes can poison the solve inputs."""
    now = time.time()
    rows = [
        f"nan,nan,nan,nan,nan,{now}",
        f"-1e30,-5,-1,-1,-1,{now}",
        f"1e300,1e300,1e300,1e300,1e300,{now}",
        f"inf,-inf,inf,-inf,inf,{now}",
        "0,0,0,0,0,-50",
    ]
    members = [_member(f"10.1.0.{i}:1", raw) for i, raw in enumerate(rows)]
    view = ClusterLoadView.from_members(members, now=now)
    for m in members:
        d = view.derate(m.address)
        assert MIN_DERATE <= d <= 1.0 and not math.isnan(d)


# ---------------------------------------------------------------------------
# LoadMonitor: thresholds + sampling loop
# ---------------------------------------------------------------------------


def test_monitor_default_thresholds_never_shed():
    m = LoadMonitor()
    m.inflight = 10_000
    m.stats.loop_lag_ms = 1e9
    assert m.shed_reason() is None


def test_monitor_shed_reasons():
    registry = types.SimpleNamespace(count_objects=lambda: 5)
    m = LoadMonitor(
        registry=registry,
        thresholds=LoadThresholds(
            max_loop_lag_ms=50.0, max_inflight=4, max_registry_objects=10
        ),
    )
    assert m.shed_reason() is None
    m.inflight = 5
    assert "inflight" in m.shed_reason()
    m.inflight = 0
    m.stats.loop_lag_ms = 80.0
    assert "lag" in m.shed_reason()
    m.stats.loop_lag_ms = 0.0
    registry.count_objects = lambda: 11
    assert "registry" in m.shed_reason()


def test_monitor_peer_garbage_cannot_trigger_shedding():
    """Shed decisions read only local measurements: a view full of insane
    peer vectors changes nothing."""
    m = LoadMonitor(thresholds=LoadThresholds(max_inflight=100))
    m.cluster_view = ClusterLoadView.from_members(
        [_member("10.0.0.9:1", "inf,inf,inf,inf,inf,1")], now=time.time()
    )
    assert m.shed_reason() is None


async def test_monitor_samples_and_pushes_view():
    pushed = []

    class FakePlacement:
        def sync_load(self, view):
            pushed.append(view)

    class FakeMembers:
        async def members(self):
            return [
                _member(
                    "10.0.0.1:1",
                    LoadVector(inflight=300, epoch=time.time()).encode(),
                )
            ]

    m = LoadMonitor(
        members_storage=FakeMembers(),
        placement=FakePlacement(),
        interval=0.01,
        view_interval=0.01,
    )
    m.request_started()
    m.request_started()
    m.request_finished()
    task = asyncio.ensure_future(m.run())
    try:
        deadline = asyncio.get_event_loop().time() + 5.0
        while asyncio.get_event_loop().time() < deadline:
            # The published depth is the sustained one: it shows once all
            # but SUSTAIN_MISSES of the last SUSTAIN_TICKS ticks read it.
            if m.stats.samples >= SUSTAIN_TICKS - SUSTAIN_MISSES and pushed:
                break
            await asyncio.sleep(0.02)
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
    assert m.stats.samples >= SUSTAIN_TICKS - SUSTAIN_MISSES
    assert m.stats.inflight == 1
    assert m.cluster_view is not None and len(m.cluster_view) == 1
    assert pushed and pushed[0].derate("10.0.0.1:1") < 1.0
    # The published snapshot round-trips through the heartbeat encoding.
    decoded = LoadVector.decode(m.encoded_snapshot())
    assert decoded is not None and decoded.inflight == 1.0


# ---------------------------------------------------------------------------
# The derate's input: lag and depth that last
# ---------------------------------------------------------------------------


def _quantized_derate(m: LoadMonitor) -> float:
    """What ``sync_load`` makes of the monitor's published vector."""
    p = _jax_provider(nodes=1)
    addr = "10.0.0.0:5000"
    p.sync_load(_view_for({addr: LoadVector.decode(m.encoded_snapshot())}))
    return p._nodes[addr].reported_derate


def test_one_isolated_hold_leaves_the_quantized_derate_where_it_was():
    """One 300 ms hold of the loop every 4 s (a solve's apply, a hand-off
    burst) is a mean lag of 300**2 / (2 * 4000) = 11 ms: one tick in four
    reads up to 300 ms, and the published lag must not follow it."""
    m = LoadMonitor(stall_threshold_ms=0)
    for period in range(6):
        for tick in range(4):
            held = tick == 1
            m.inflight = 120 if held else 1  # the hold's backlog, that tick only
            m._sample(300.0 if held else 0.4)
            assert _quantized_derate(m) == 1.0, (period, tick)
    assert m.stats.loop_lag_ms > 20.0  # the EMA did follow it


def test_a_loop_late_on_every_tick_is_derated_within_five_ticks():
    m = LoadMonitor(stall_threshold_ms=0)
    for _ in range(3):
        m._sample(0.0)
    seen = []
    for _ in range(5):
        m._sample(100.0)
        seen.append(_quantized_derate(m))
    assert seen[-1] <= 0.5, seen
    assert seen[0] == 1.0  # and not by the first late tick alone
    # It lasts while the lag does, and goes when most ticks are on time.
    for _ in range(SUSTAIN_TICKS):
        m._sample(100.0)
    assert _quantized_derate(m) == 0.5
    for _ in range(SUSTAIN_TICKS - SUSTAIN_MISSES):
        m._sample(0.2)
    assert _quantized_derate(m) == 1.0


def test_shedding_still_reads_the_smoothed_lag_and_the_live_depth():
    """``Service`` sheds on the node's own smoothed lag (0.3 a tick) and on
    the requests in flight right now, as before: shedding protects the node
    in the moment, the derate prices it for the next solve."""
    m = LoadMonitor(
        thresholds=LoadThresholds(max_loop_lag_ms=50.0, max_inflight=4),
        stall_threshold_ms=0,
    )
    m._sample(200.0)
    assert math.isclose(m.stats.loop_lag_ms, 60.0)
    assert "loop lag" in m.shed_reason()
    assert m.stats.loop_lag_sustained_ms == 0.0
    m.stats.loop_lag_ms = 0.0
    for _ in range(5):
        m.request_started()
    assert "inflight" in m.shed_reason()
    assert m.snapshot().inflight == 0.0  # no tick has seen that depth yet


def test_the_watchdog_drops_the_frame_it_captured():
    """A captured frame pins every local of the stalled call chain; the
    watchdog keeps the formatted stack and nothing else (D16)."""
    import gc
    import threading
    import weakref

    class Held:
        pass

    m = LoadMonitor(stall_threshold_ms=20.0, stall_cooldown=0.0)
    m._heartbeat = time.monotonic() - 10.0
    dog = _StallWatchdog(m, threading.get_ident(), interval=0.01)
    ref = []

    def stalled_call():
        held = Held()
        ref.append(weakref.ref(held))
        dog.start()
        deadline = time.monotonic() + 5.0
        while m._pending_stall is None and time.monotonic() < deadline:
            time.sleep(0.01)

    stalled_call()
    try:
        assert m._pending_stall is not None
        assert "stalled_call" in m._pending_stall["stack"]
        gc.collect()
        assert ref[0]() is None  # the watchdog thread is still running
    finally:
        dog.stop_event.set()
        dog.join(2.0)


# ---------------------------------------------------------------------------
# Solver consumer: capacity derating + per-object move pricing
# ---------------------------------------------------------------------------


def _jax_provider(nodes=2, **kw):
    p = JaxObjectPlacement(node_axis_size=16, **kw)
    for i in range(nodes):
        p.register_node(f"10.0.0.{i}:5000")
    return p


def _view_for(loads: dict[str, LoadVector]) -> ClusterLoadView:
    now = time.time()
    members = []
    for addr, vec in loads.items():
        vec.epoch = now
        members.append(_member(addr, vec.encode()))
    return ClusterLoadView.from_members(members, now=now)


def test_sync_load_derates_in_quantized_steps_without_moving_the_epoch():
    p = _jax_provider()
    a, b = "10.0.0.0:5000", "10.0.0.1:5000"
    epoch0 = p._epoch
    # inflight 1792 -> derate 1/(1+7) = 0.125 (a quantization grid point).
    p.sync_load(_view_for({a: LoadVector(inflight=1792), b: LoadVector()}))
    assert p._nodes[a].reported_derate == 0.125
    assert p._nodes[b].reported_derate == 1.0
    # A tiny wobble inside the same 1/8 bucket re-prices nothing.
    p.sync_load(_view_for({a: LoadVector(inflight=1800), b: LoadVector()}))
    assert p._nodes[a].reported_derate == 0.125
    # view=None resets to full capacity.
    p.sync_load(None)
    assert p._nodes[a].reported_derate == 1.0
    # A derate is a price, not a directory fact: the epoch guards seats
    # and liveness only.
    assert p._epoch == epoch0


def test_a_refresh_prices_the_nodes_that_report_and_those_off_full_price_only():
    """Eight daemons and eight monitors of one process refresh the view every
    second or two; priced node by node that was 0.7 ms a call at 1,024 nodes,
    and calls that fall together hold the loop long enough for a sibling's
    tick to read it as lag. Only a node the view has an entry for, or one
    that stands off full price, can step."""
    p = _jax_provider(nodes=12)
    a, b, c = "10.0.0.0:5000", "10.0.0.1:5000", "10.0.0.2:5000"
    view = _view_for({a: LoadVector(inflight=1792), b: LoadVector()})
    asked = []
    real = view.derate
    view.derate = lambda addr: asked.append(addr) or real(addr)
    p.sync_load(view)
    assert sorted(asked) == [a, b] and p._nodes[a].reported_derate == 0.125
    # A node whose report is gone from the view goes back to full price...
    asked.clear()
    gone = _view_for({b: LoadVector()})
    real_gone = gone.derate
    gone.derate = lambda addr: asked.append(addr) or real_gone(addr)
    p.sync_load(gone)
    assert sorted(asked) == [a, b] and p._nodes[a].reported_derate == 1.0
    assert p._nodes[c].reported_derate == 1.0
    # ...and a view that is no ClusterLoadView (no ``entries``) prices every node.
    class Flat:
        def derate(self, addr):
            asked.append(addr)
            return 0.5

    asked.clear()
    p.sync_load(Flat())
    assert len(asked) == 12 and p._nodes[c].reported_derate == 0.5


def test_a_view_is_built_from_the_rows_that_report_load():
    rows = [_member(f"10.0.0.{i}:5000", "") for i in range(5)]
    rows.append(_member("10.0.0.9:5000", LoadVector(inflight=3, epoch=time.time()).encode()))
    view = ClusterLoadView.from_members(rows)
    assert list(view.entries) == ["10.0.0.9:5000"] and view.derate("10.0.0.1:5000") == 1.0


def test_a_price_on_the_edge_between_two_steps_does_not_flap():
    """A node leaves the lattice step it is on only for a price at least 3/4
    of a step away: a server whose loop runs ~7 ms late (its co-located
    siblings' ticks ahead of its own) prices at 0.9375, the edge between 1.0
    and 0.875, and every call would otherwise step it one way or the other."""
    p = _jax_provider()
    a, b = "10.0.0.0:5000", "10.0.0.1:5000"
    for lag in (6.0, 7.5, 6.2, 7.9, 6.5, 9.0, 5.0):  # 0.917 .. 0.952: around the edge
        p.sync_load(_view_for({a: LoadVector(loop_lag_ms=lag), b: LoadVector()}))
        assert p._nodes[a].reported_derate == 1.0
    assert p.place_gauges()["rio.load.derate_steps"] == 0.0
    # A price that IS another step's is taken at once, to the nearest step...
    p.sync_load(_view_for({a: LoadVector(loop_lag_ms=16.0), b: LoadVector()}))  # 0.862
    assert p._nodes[a].reported_derate == 0.875
    p.sync_load(_view_for({a: LoadVector(loop_lag_ms=100.0), b: LoadVector()}))  # 0.5
    assert p._nodes[a].reported_derate == 0.5
    assert p.place_gauges()["rio.load.derate_steps"] == 4.0  # 1 + 3 lattice steps
    # ...and held on the way back as on the way there.
    for lag in (80.0, 90.0, 72.0):  # 0.556 .. 0.581: nearer 0.5 than 3/4 of a step
        p.sync_load(_view_for({a: LoadVector(loop_lag_ms=lag), b: LoadVector()}))
        assert p._nodes[a].reported_derate == 0.5
    p.sync_load(_view_for({a: LoadVector(loop_lag_ms=0.0), b: LoadVector()}))
    assert p._nodes[a].reported_derate == 1.0 and p._nodes[b].reported_derate == 1.0


async def test_derate_flip_does_not_discard_a_solve_in_flight():
    """A server sharing the directory's process reads a long solve's host
    work as its own load; the derate flip that follows must not throw the
    solve away (on the chip it discarded every cold-compile solve)."""
    p = _jax_provider(mode="sinkhorn")
    a, b = "10.0.0.0:5000", "10.0.0.1:5000"
    await p.assign_batch([ObjectId("T", str(i)) for i in range(64)])
    real_to_thread = asyncio.to_thread

    async def flip_mid_solve(fn, *args):
        out = await real_to_thread(fn, *args)
        p.sync_load(_view_for({a: LoadVector(inflight=1792), b: LoadVector()}))
        return out

    asyncio.to_thread = flip_mid_solve
    try:
        await p.rebalance(delta=False)
    finally:
        asyncio.to_thread = real_to_thread
    assert p._nodes[a].reported_derate == 0.125
    assert not p.stats.discarded
    # The committed solve holds the capacities it snapshotted (even);
    # the next one prices the derate in.
    counts = [len(p._by_node.get(i, ())) for i in range(2)]
    assert counts == [32, 32]
    await p.rebalance(delta=False)
    assert not p.stats.discarded
    assert len(p._by_node.get(0, ())) < len(p._by_node.get(1, ()))


async def test_assign_batch_respects_derated_capacity():
    p = _jax_provider()
    a, b = "10.0.0.0:5000", "10.0.0.1:5000"
    p.sync_load(_view_for({a: LoadVector(inflight=1792), b: LoadVector()}))
    addrs = await p.assign_batch([ObjectId("T", str(i)) for i in range(160)])
    counts = {a: addrs.count(a), b: addrs.count(b)}
    # Capacity columns are 0.125 vs 1.0: the healthy node takes the bulk.
    assert counts[b] > counts[a] * 3, counts
    assert counts[a] > 0  # floor: the hot node never vanishes entirely


async def test_sync_load_chaos_view_cannot_poison_assignment():
    p = _jax_provider()
    now = time.time()
    members = [
        _member("10.0.0.0:5000", f"nan,inf,-1,nan,inf,{now}"),
        _member("10.0.0.1:5000", "1e300,-1e300,nan,inf,0,-5"),
    ]
    p.sync_load(ClusterLoadView.from_members(members, now=now))
    for slot in p._nodes.values():
        assert 0.1 <= slot.reported_derate <= 1.0
    addrs = await p.assign_batch([ObjectId("T", str(i)) for i in range(64)])
    assert set(addrs) <= {"10.0.0.0:5000", "10.0.0.1:5000"}


async def test_rebalance_affinity_pricing_keeps_hot_objects():
    """Acceptance: a hot/heavy actor is assigned differently under
    per-object pricing than under uniform move cost.

    16 objects all seated on node a; node b joins with 3x the capacity, so
    the quota repair forces 12 of 16 to move. Under uniform cost the
    evicted 12 are an arbitrary choice; with object_costs pricing the 4
    hot actors 16x dearer, the solver must evict only cold ones.
    """
    a, b = "10.0.0.0:5000", "10.0.0.1:5000"
    keys = [f"T.{i}" for i in range(16)]
    hot = {keys[3], keys[7], keys[11], keys[15]}

    def object_costs(ks):
        return np.asarray([16.0 if k in hot else 1.0 for k in ks], np.float32)

    async def seed(p):
        for k in keys:
            t, _, i = k.partition(".")
            await p.update(ObjectPlacementItem(ObjectId(t, i), a))

    priced = JaxObjectPlacement(
        node_axis_size=16, mode="sinkhorn", move_cost=0.5,
        object_costs=object_costs,
    )
    priced.register_node(a, capacity=1.0)
    priced.register_node(b, capacity=3.0)
    await seed(priced)
    moved = await priced.rebalance(mode="sinkhorn")
    assert moved == 12
    stayers = {
        k for k in keys
        if await priced.lookup(ObjectId(*k.split("."))) == a
    }
    assert stayers == hot  # every survivor on a is a hot actor
    # Non-uniform prices must route the dense pipeline, not the collapse.
    assert priced.stats.mode == "sinkhorn"

    uniform = JaxObjectPlacement(
        node_axis_size=16, mode="sinkhorn", move_cost=0.5,
    )
    uniform.register_node(a, capacity=1.0)
    uniform.register_node(b, capacity=3.0)
    await seed(uniform)
    moved_u = await uniform.rebalance(mode="sinkhorn")
    assert moved_u == 12
    # Uniform pricing keeps the collapsed O(M^2) fast path (solver parity).
    assert uniform.stats.mode == "sinkhorn+collapsed"


async def test_rebalance_uniform_object_costs_keep_fast_path():
    """A hook returning all-equal weights is semantically the scalar
    move_cost: the collapsed fast path must survive it."""
    p = JaxObjectPlacement(
        node_axis_size=16, mode="sinkhorn", move_cost=0.5,
        object_costs=lambda ks: np.ones((len(ks),), np.float32),
    )
    p.register_node("10.0.0.0:5000")
    p.register_node("10.0.0.1:5000")
    for i in range(8):
        await p.update(ObjectPlacementItem(ObjectId("T", str(i)), "10.0.0.0:5000"))
    await p.rebalance(mode="sinkhorn")
    assert p.stats.mode == "sinkhorn+collapsed"


async def test_rebalance_broken_object_costs_degrade_to_uniform():
    """A hook that raises (or returns the wrong shape) must never break a
    rebalance — pricing degrades to uniform."""
    calls = {"n": 0}

    def broken(ks):
        calls["n"] += 1
        raise RuntimeError("telemetry offline")

    p = JaxObjectPlacement(
        node_axis_size=16, mode="sinkhorn", move_cost=0.5, object_costs=broken,
    )
    p.register_node("10.0.0.0:5000")
    p.register_node("10.0.0.1:5000")
    for i in range(8):
        await p.update(ObjectPlacementItem(ObjectId("T", str(i)), "10.0.0.0:5000"))
    moved = await p.rebalance(mode="sinkhorn")
    assert calls["n"] == 1
    assert moved == 4
    assert p.stats.mode == "sinkhorn+collapsed"


# ---------------------------------------------------------------------------
# Overload integration: ServerBusy shed -> client backoff -> healthy node
# ---------------------------------------------------------------------------


@message(name="load.Ping")
class Ping:
    pass


@message(name="load.Pong")
class Pong:
    address: str = ""


class Echo(ServiceObject):
    @handler
    async def ping(self, msg: Ping, ctx: AppData) -> Pong:
        return Pong(address=ctx.get(ServerInfo).address)


def build_registry() -> Registry:
    return Registry().add_type(Echo)


def test_overloaded_server_sheds_and_client_completes_elsewhere():
    """Acceptance: a saturated server sheds with ServerBusy; the client
    backs off, avoids it, and every request completes on the healthy
    member."""

    async def body(cluster: Cluster):
        s0, s1 = cluster.servers
        # Saturate s0 after boot: with max_inflight=0 every fresh
        # activation there sheds (the in-flight request itself counts).
        s0.load_monitor.thresholds = LoadThresholds(max_inflight=0)
        client = cluster.client()
        try:
            outs = [
                await client.send(Echo, f"e{i}", Ping(), returns=Pong)
                for i in range(20)
            ]
        finally:
            client.close()
        # Every request completed, all on the healthy node.
        assert {o.address for o in outs} == {s1.local_address}
        # The busy node really shed (20 random 2-way picks: P(no hit on
        # s0) = 2^-20) and the client answered with busy retries.
        assert s0.load_monitor.stats.sheds > 0
        assert client.stats.busy_retries > 0
        assert s1.load_monitor.stats.sheds == 0
        # Shed ids were un-seated, not parked: directory rows point at s1.
        assert (
            await cluster.allocation_address("Echo", "e0") == s1.local_address
        )
        assert not s0.registry.has("Echo", "e0")

    asyncio.run(
        run_integration_test(body, registry_builder=build_registry, num_servers=2)
    )


def test_activated_objects_keep_serving_while_shedding():
    """Only would-be activations shed: an object already live on the busy
    node keeps answering (bouncing it would redirect-ping-pong)."""

    async def body(cluster: Cluster):
        s0, s1 = cluster.servers
        client = cluster.client()
        try:
            # Seat one object on s0 while healthy.
            out = None
            for i in range(40):
                out = await client.send(Echo, f"warm{i}", Ping(), returns=Pong)
                if out.address == s0.local_address:
                    warm_id = f"warm{i}"
                    break
            assert out is not None and out.address == s0.local_address
            s0.load_monitor.thresholds = LoadThresholds(max_inflight=0)
            out = await client.send(Echo, warm_id, Ping(), returns=Pong)
            assert out.address == s0.local_address  # still served locally
        finally:
            client.close()

    asyncio.run(
        run_integration_test(body, registry_builder=build_registry, num_servers=2)
    )


def test_a_server_without_a_load_monitor_leaves_the_collector_alone():
    """``load_monitor=False``: no watcher, so no full collection's survivors
    are set aside (the switch an operator has for the interpreter's own
    behaviour)."""
    import gc

    from rio_tpu import tracing

    async def body(cluster: Cluster):
        assert all(s.load_monitor is None for s in cluster.servers)
        assert tracing._on_gc not in gc.callbacks
        client = cluster.client()
        try:
            await client.send(Echo, "e0", Ping(), returns=Pong)
            gc.collect()
            # What the interpreter keeps there itself (its immortal objects).
            own = gc.get_freeze_count()
            assert own < 1_000
            await client.send(Echo, "e1", Ping(), returns=Pong)
            gc.collect()
            assert gc.get_freeze_count() == own
            listed = gc.get_objects()
            assert all(any(o is s for o in listed) for s in cluster.servers)
        finally:
            client.close()

    asyncio.run(
        run_integration_test(
            body, registry_builder=build_registry, num_servers=2,
            server_kwargs={"load_monitor": False},
        )
    )
