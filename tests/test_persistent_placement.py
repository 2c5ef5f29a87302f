"""PersistentJaxObjectPlacement: solver speed + write-behind durability.

The migration gap this closes: a rio-rs user coming from
SqliteObjectPlacement had directory durability; the plain
JaxObjectPlacement trades it for speed. The bridge must (a) restore the
whole directory from the backing store at prepare(), (b) write every
mutation path behind (allocation, update, remove, clean_server,
rebalance), (c) survive backing-store outages without losing marks.
"""

import asyncio

import pytest

from rio_tpu import ObjectId, ObjectPlacementItem
from rio_tpu.object_placement import LocalObjectPlacement
from rio_tpu.object_placement.persistent import PersistentJaxObjectPlacement
from rio_tpu.object_placement.sqlite import SqliteObjectPlacement


def _provider(backing, **kw):
    p = PersistentJaxObjectPlacement(
        backing, flush_interval=0.01, mode="greedy", **kw
    )
    for i in range(4):
        p.register_node(f"10.9.0.{i}:5000")
    return p


async def _settled_flush(p):
    # One interval for the flusher's coalescing sleep, then force.
    await asyncio.sleep(0.03)
    await p.flush()


async def test_restart_restores_directory(tmp_path):
    backing = SqliteObjectPlacement(str(tmp_path / "dir.db"))
    p1 = _provider(backing)
    await p1.prepare()
    ids = [ObjectId("Game", str(i)) for i in range(200)]
    addrs = await p1.assign_batch(ids)
    await _settled_flush(p1)
    await p1.aclose()

    # "Restart": a fresh provider over the same backing store sees every
    # seat — no lazy re-allocation needed for the restored population.
    p2 = _provider(SqliteObjectPlacement(str(tmp_path / "dir.db")))
    await p2.prepare()
    assert p2.count() == len(ids)
    assert await p2.lookup_batch(ids) == addrs
    # Restored rows are already durable: nothing is dirty after prepare.
    assert p2._dirty == {}
    # And stickiness holds across the restart (same seats re-asserted).
    again = await p2.assign_batch(ids)
    assert again == addrs
    await p2.aclose()


async def test_every_mutation_path_writes_behind(tmp_path):
    backing = SqliteObjectPlacement(str(tmp_path / "dir.db"))
    p = _provider(backing)
    await p.prepare()

    # allocation path
    ids = [ObjectId("T", str(i)) for i in range(40)]
    await p.assign_batch(ids)
    # manual update path
    await p.update(ObjectPlacementItem(ObjectId("T", "manual"), "10.9.0.1:5000"))
    await _settled_flush(p)
    assert await backing.lookup(ObjectId("T", "manual")) == "10.9.0.1:5000"
    rows = await backing.items()
    assert len(rows) == 41

    # remove path
    await p.remove(ObjectId("T", "manual"))
    # clean_server path (drops everything on that node)
    victim = await p.lookup(ids[0])
    on_victim = [i for i in ids if await p.lookup(i) == victim]
    await p.clean_server(victim)
    await _settled_flush(p)
    assert await backing.lookup(ObjectId("T", "manual")) is None
    for oid in on_victim:
        assert await backing.lookup(oid) is None

    # rebalance path: kill a node, re-solve; backing follows the movers
    p.sync_members([f"10.9.0.{i}:5000" for i in range(4) if i != 2])
    await p.rebalance()
    await _settled_flush(p)
    live = {f"10.9.0.{i}:5000" for i in range(4) if i != 2}
    for item in await backing.items():
        assert item.server_address in live
    await p.aclose()


async def test_restore_counts_load_and_quarantines_ghost_nodes(tmp_path):
    """Two restart hazards: (a) restored population must count as node
    load, or the next waterfill treats the cluster as empty and piles onto
    the fullest node; (b) addresses the restore itself invents are hearsay
    — the node may have died while we were down — so they start DEAD and
    never attract NEW objects (their restored rows stand until re-seat)."""
    backing = SqliteObjectPlacement(str(tmp_path / "dir.db"))
    await backing.prepare()
    for i in range(90):  # heavy restored load on node A
        await backing.update(
            ObjectPlacementItem(ObjectId("T", f"a{i}"), "10.9.0.0:5000")
        )
    for i in range(30):  # rows on a node that died while we were down
        await backing.update(
            ObjectPlacementItem(ObjectId("T", f"g{i}"), "10.9.9.9:1")
        )
    p = PersistentJaxObjectPlacement(backing, flush_interval=0.01, mode="greedy")
    p.register_node("10.9.0.0:5000")
    p.register_node("10.9.0.1:5000")
    await p.prepare()
    assert p.count() == 120
    where = await p.assign_batch([ObjectId("N", str(i)) for i in range(40)])
    # (b) the ghost never receives new objects...
    assert "10.9.9.9:1" not in where
    # ...but its restored placements still resolve (lazy re-seat covers).
    assert await p.lookup(ObjectId("T", "g0")) == "10.9.9.9:1"
    # (a) the empty live node absorbs the new allocation (load counted).
    from collections import Counter

    counts = Counter(where)
    assert counts["10.9.0.1:5000"] >= 35, counts
    await p.aclose()


async def test_aclose_mid_flush_cancellation_loses_nothing():
    """aclose() cancelling the flusher MID-write must put the in-flight
    dirty set back (flush's except BaseException) so the final flush lands
    it — except Exception would silently drop it at shutdown."""

    class SlowBacking(LocalObjectPlacement):
        def __init__(self):
            super().__init__()
            self.calls = 0
            self.entered = asyncio.Event()

        async def update_batch(self, items):
            self.calls += 1
            if self.calls == 1:
                self.entered.set()
                await asyncio.Event().wait()  # parked until cancelled
            await super().update_batch(items)

    backing = SlowBacking()
    p = _provider(backing)
    await p.prepare()
    await p.update(ObjectPlacementItem(ObjectId("T", "a"), "10.9.0.0:5000"))
    await asyncio.wait_for(backing.entered.wait(), 5)  # flusher mid-write
    await asyncio.wait_for(p.aclose(), 5)
    assert backing.calls == 2
    assert await backing.lookup(ObjectId("T", "a")) == "10.9.0.0:5000"


async def test_flush_failure_keeps_marks_and_retries():
    class FlakyBacking(LocalObjectPlacement):
        def __init__(self):
            super().__init__()
            self.fail_next = 0

        async def update_batch(self, items):
            if self.fail_next > 0:
                self.fail_next -= 1
                raise ConnectionError("backing down")
            await super().update_batch(items)

    backing = FlakyBacking()
    p = _provider(backing)
    await p.prepare()
    backing.fail_next = 1
    await p.update(ObjectPlacementItem(ObjectId("T", "a"), "10.9.0.0:5000"))
    with pytest.raises(ConnectionError):
        await p.flush()
    # The mark survived the failed flush...
    assert p._dirty == {"T.a": "10.9.0.0:5000"}
    # ...and the next flush lands it.
    assert await p.flush() == 1
    assert await backing.lookup(ObjectId("T", "a")) == "10.9.0.0:5000"
    await p.aclose()


async def test_background_flusher_runs_without_manual_flush(tmp_path):
    backing = SqliteObjectPlacement(str(tmp_path / "dir.db"))
    p = _provider(backing)
    await p.prepare()
    await p.assign_batch([ObjectId("T", str(i)) for i in range(10)])
    for _ in range(100):
        if len(await backing.items()) == 10:
            break
        await asyncio.sleep(0.02)
    assert len(await backing.items()) == 10
    await p.aclose()


async def test_promotion_after_cold_restart_keeps_surviving_standbys(tmp_path):
    """Mirror-miss promotion must rebuild the standby row from the BACKING's
    post-CAS row, not from an empty host mirror — with k>=2 the old rebuild
    flushed [] over the surviving seats, silently dropping durable standbys
    until anti-entropy re-placed them."""
    backing = SqliteObjectPlacement(str(tmp_path / "dir.db"))
    p1 = _provider(backing)
    await p1.prepare()
    oid = ObjectId("Game", "g0")
    await p1.update(ObjectPlacementItem(oid, "10.9.0.0:5000"))
    await p1.set_standbys(oid, ["10.9.0.1:5000", "10.9.0.2:5000"])
    await _settled_flush(p1)
    await p1.aclose()

    # Restart: standby rows restore lazily, so the mirror is cold when the
    # failover CAS arrives.
    p2 = _provider(SqliteObjectPlacement(str(tmp_path / "dir.db")))
    await p2.prepare()
    assert await p2.promote_standby(oid, "10.9.0.1:5000", 0) == 1
    assert await p2.standbys(oid) == (["10.9.0.2:5000"], 1)
    # The write-behind flush persists the SURVIVING seat, not an empty set.
    await _settled_flush(p2)
    assert await p2._backing.standbys(oid) == (["10.9.0.2:5000"], 1)
    await p2.aclose()


async def test_bulk_seats_are_written_behind_and_none_while_restoring(tmp_path):
    """``assign_batch`` seats a chunk through the bulk seam (``_seat_new``):
    the subclass must mark every key of it, and none during a restore."""
    import numpy as np

    backing = SqliteObjectPlacement(str(tmp_path / "dir.db"))
    p = _provider(backing)
    await p.prepare()
    ids = [ObjectId("Bulk", str(i)) for i in range(300)]
    addrs = await p.assign_batch(ids)
    assert p._dirty == {str(o): a for o, a in zip(ids, addrs)}
    assert p.place_gauges()["rio.place.bulk_rows"] == 300
    await _settled_flush(p)
    assert p._dirty == {}
    stored = {str(i.object_id): i.server_address for i in await backing.items()}
    assert stored == {str(o): a for o, a in zip(ids, addrs)}

    p._restoring = True
    try:
        p._seat_new(["Bulk.r0", "Bulk.r1"], np.array([0, 1], np.int32))
    finally:
        p._restoring = False
    assert p._dirty == {} and p.count() == 302
    p._seat_new([], np.array([], np.int32))  # an empty chunk wakes nobody
    assert p._dirty == {}
    await p.aclose()
