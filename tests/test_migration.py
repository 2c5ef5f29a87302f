"""Live-migration handoff protocol: single-activation fencing, state
transfer, rebalancer actuation, and failure-path recovery.

The e2e test is the acceptance bar for the subsystem: a placement-daemon
rebalance moves seated stateful objects between live nodes under concurrent
client traffic with zero lost updates and zero double-activations, and
reminder-shard seat rows flow through the same ``apply_moves`` path.
"""

import asyncio
import random
import time

import pytest

from rio_tpu import (
    AdminCommand,
    AppData,
    LocalObjectPlacement,
    LocalStorage,
    Registry,
    ServiceObject,
    handler,
    message,
    type_name,
)
from rio_tpu.commands import ServerInfo
from rio_tpu.errors import ObjectNotFound
from rio_tpu.migration import (
    CONTROL_TYPE,
    INBOX_TYPE,
    InstallState,
    MigrationAck,
    MigrationConfig,
    MigrationManager,
    MigrationStats,
)
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement
from rio_tpu.placement_daemon import PlacementDaemonConfig
from rio_tpu.protocol import ResponseError
from rio_tpu.registry import ObjectId
from rio_tpu.reminders.daemon import SHARD_TYPE
from rio_tpu.state import LocalState, StateProvider, managed_state

from .server_utils import (
    Cluster,
    run_integration_test,
    wait_for_active_members,
)

# Module-level activation guards, reset by each test that uses them.
ACTIVATIONS: dict[str, int] = {}  # id -> lifetime LOAD count
ACTIVE: dict[str, str] = {}  # id -> address currently holding a live instance
DOUBLE: list[str] = []  # ids that activated while already active somewhere


def _reset_guards() -> None:
    ACTIVATIONS.clear()
    ACTIVE.clear()
    DOUBLE.clear()


@message
class Add:
    amount: int = 0


@message
class Get:
    pass


@message
class Totals:
    total: int = 0
    hot: int = 0
    address: str = ""


@message
class CounterState:
    total: int = 0


class Counter(ServiceObject):
    """Stateful actor with both managed and volatile migratable state.

    ``hot`` mirrors ``state.total`` but lives only in memory: after any
    number of coordinated handoffs the two must still be equal — a fresh
    (non-migrated) activation would reset ``hot`` to 0 and expose a lost
    volatile snapshot.
    """

    state = managed_state(CounterState)

    def __init__(self):
        self.hot = 0

    def __migrate_state__(self):
        return {"hot": self.hot}

    def __restore_state__(self, value):
        self.hot = int(value["hot"])

    async def after_load(self, ctx: AppData) -> None:
        ACTIVATIONS[self.id] = ACTIVATIONS.get(self.id, 0) + 1
        addr = ctx.get(ServerInfo).address
        if self.id in ACTIVE:
            DOUBLE.append(self.id)
        ACTIVE[self.id] = addr

    async def before_shutdown(self, ctx: AppData) -> None:
        ACTIVE.pop(self.id, None)

    @handler
    async def add(self, msg: Add, ctx: AppData) -> Totals:
        self.state.total += msg.amount
        self.hot += msg.amount
        await self.save_state(ctx)
        return Totals(
            total=self.state.total, hot=self.hot, address=ctx.get(ServerInfo).address
        )

    @handler
    async def get(self, msg: Get, ctx: AppData) -> Totals:
        return Totals(
            total=self.state.total, hot=self.hot, address=ctx.get(ServerInfo).address
        )


def build_registry() -> Registry:
    return Registry().add_type(Counter)


# ---------------------------------------------------------------------------
# Admin-command handoff: managed + volatile state survive, stats move
# ---------------------------------------------------------------------------


def test_admin_migrate_moves_state_and_volatile():
    _reset_guards()
    state = LocalState()

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            out = await client.send(Counter, "c1", Add(amount=7), returns=Totals)
            source_addr = out.address
            out = await client.send(Counter, "c1", Add(amount=3), returns=Totals)
            assert (out.total, out.hot) == (10, 10)

            source = next(
                s for s in cluster.servers if s.local_address == source_addr
            )
            target = next(
                s for s in cluster.servers if s.local_address != source_addr
            )
            source.admin_sender().send(
                AdminCommand.migrate("Counter", "c1", target.local_address)
            )
            deadline = asyncio.get_event_loop().time() + 10.0
            while asyncio.get_event_loop().time() < deadline:
                if source.migration_manager.stats.completed:
                    break
                await asyncio.sleep(0.02)
            assert source.migration_manager.stats.completed == 1
            assert source.migration_manager.stats.started == 1
            assert source.migration_manager.stats.aborted == 0
            assert source.migration_manager.stats.state_bytes > 0
            assert target.migration_manager.stats.installs == 1

            # Directory flipped; source no longer holds the instance.
            assert (
                await cluster.allocation_address("Counter", "c1")
                == target.local_address
            )
            assert not source.registry.has("Counter", "c1")

            # The next request activates on the target with BOTH kinds of
            # state intact — managed via the backend, volatile via the
            # inline transfer.
            out = await client.send(Counter, "c1", Add(amount=1), returns=Totals)
            assert out.address == target.local_address
            assert (out.total, out.hot) == (11, 11)
            assert ACTIVATIONS["c1"] == 2
            assert DOUBLE == []
        finally:
            client.close()

    async def wrapped(cluster: Cluster):
        for s in cluster.servers:
            s.app_data.set(state, as_type=StateProvider)
        await body(cluster)

    asyncio.run(
        run_integration_test(wrapped, registry_builder=build_registry, num_servers=2)
    )


# ---------------------------------------------------------------------------
# E2E acceptance: daemon rebalance = live handoffs under concurrent traffic
# ---------------------------------------------------------------------------


def test_rebalance_actuates_live_handoffs_under_traffic():
    """Boot a third node into a loaded 2-node cluster: the placement daemon
    re-solves on the liveness change and every solver move runs as a
    coordinated handoff between LIVE nodes, while clients keep writing.
    Zero lost updates, zero double-activations, volatile state rides along,
    and a reminder-shard seat row flips through the same move path."""
    _reset_guards()
    state = LocalState()
    placement = JaxObjectPlacement(mode="greedy", move_cost=0.5)
    daemon_cfg = PlacementDaemonConfig(
        poll_interval=0.1, debounce=0.05, min_rebalance_interval=0.1
    )
    n_objects = 12
    keys = [f"c{i}" for i in range(n_objects)]

    async def body(cluster: Cluster):
        client = cluster.client()
        third = None
        third_task = None
        acked = {k: 0 for k in keys}
        failures: list[str] = []
        stop_traffic = asyncio.Event()

        async def traffic():
            while not stop_traffic.is_set():
                k = random.choice(keys)
                for attempt in range(3):
                    try:
                        await client.send(Counter, k, Add(amount=1), returns=Totals)
                        acked[k] += 1
                        break
                    except Exception:
                        if attempt == 2:
                            failures.append(k)
                        await asyncio.sleep(0.05)
                await asyncio.sleep(0.005)

        try:
            for k in keys:
                await client.send(Counter, k, Add(amount=1), returns=Totals)
                acked[k] += 1
            # Seed a reminder-shard seat row beside the object population.
            from rio_tpu.object_placement import ObjectPlacementItem

            shard_oid = ObjectId(SHARD_TYPE, "3")
            await placement.update(
                ObjectPlacementItem(shard_oid, cluster.addresses[0])
            )

            traffic_task = asyncio.create_task(traffic())
            await asyncio.sleep(0.3)

            # Boot the third node mid-traffic: its registration is the
            # liveness change that arms every daemon.
            from rio_tpu import Server
            from rio_tpu.cluster.membership_protocol import LocalClusterProvider

            third = Server(
                address="127.0.0.1:0",
                registry=build_registry(),
                cluster_provider=LocalClusterProvider(cluster.members),
                object_placement_provider=placement,
                app_data=AppData().set(state, as_type=StateProvider),
                placement_daemon=True,
                placement_daemon_config=daemon_cfg,
            )
            await third.prepare()
            await third.bind()
            third_task = asyncio.create_task(third.run())
            await wait_for_active_members(cluster.members, 3)

            managers = [s.migration_manager for s in cluster.servers] + [
                third.migration_manager
            ]
            deadline = asyncio.get_event_loop().time() + 20.0
            while asyncio.get_event_loop().time() < deadline:
                if sum(m.stats.completed for m in managers) > 0:
                    break
                await asyncio.sleep(0.05)
            assert sum(m.stats.completed for m in managers) > 0, (
                "no coordinated handoff ran after the liveness change"
            )
            await asyncio.sleep(0.5)  # let a little post-move traffic land

            stop_traffic.set()
            await traffic_task
            assert not failures, f"writes failed outright: {failures}"

            # Zero lost updates + volatile state followed every move.
            all_addrs = set(cluster.addresses) | {third.local_address}
            for k in keys:
                out = await client.send(Counter, k, Get(), returns=Totals)
                assert out.total == acked[k], (
                    f"{k}: {acked[k]} acked writes but total={out.total}"
                )
                assert out.hot == out.total, (
                    f"{k}: volatile state lost in handoff "
                    f"(hot={out.hot}, total={out.total})"
                )
                assert out.address in all_addrs
            assert DOUBLE == [], f"double activations: {DOUBLE}"

            # Reminder-shard rows ride the same apply_moves path: ask a
            # coordinator to move the seeded seat row; with no live
            # activation to hand off it must flip the row directly.
            mover = cluster.servers[0].migration_manager
            src = await placement.lookup(shard_oid)
            dst = next(a for a in sorted(all_addrs) if a != src)
            moved = await mover.apply_moves([(f"{SHARD_TYPE}.3", src, dst)])
            assert moved == 1
            assert await placement.lookup(shard_oid) == dst
        finally:
            stop_traffic.set()
            client.close()
            if third_task is not None:
                third_task.cancel()
                await asyncio.gather(third_task, return_exceptions=True)

    async def wrapped(cluster: Cluster):
        for s in cluster.servers:
            s.app_data.set(state, as_type=StateProvider)
        await body(cluster)

    asyncio.run(
        run_integration_test(
            wrapped,
            registry_builder=build_registry,
            num_servers=2,
            placement=placement,
            timeout=60.0,
            server_kwargs={
                "placement_daemon": True,
                "placement_daemon_config": daemon_cfg,
            },
        )
    )


# ---------------------------------------------------------------------------
# Batched bursts + target-initiated prefetch: a grouped drain moves many
# keys through few RPCs and skips the in-window transfer on unchanged state
# ---------------------------------------------------------------------------


def test_batched_drain_prefetch_hits_skip_pinned_transfer():
    """Drain every key off one node through apply_moves: the plan is grouped
    into MigrateBatch bursts (chunked at batch_size), the target prefetches
    each volatile snapshot before the pin, and — with no traffic mutating
    state between prefetch and pin — every handoff is a prefetch HIT: zero
    pin-time installs, volatile state still intact on the target."""
    _reset_guards()
    state = LocalState()
    n_objects = 12
    keys = [f"b{i}" for i in range(n_objects)]

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            owners: dict[str, list[str]] = {s.local_address: [] for s in cluster.servers}
            for k in keys:
                out = await client.send(Counter, k, Add(amount=3), returns=Totals)
                owners[out.address].append(k)
            source_addr = max(owners, key=lambda a: len(owners[a]))
            drained = owners[source_addr]
            source = next(s for s in cluster.servers if s.local_address == source_addr)
            target = next(s for s in cluster.servers if s.local_address != source_addr)

            moves = [
                (f"Counter.{k}", source_addr, target.local_address) for k in drained
            ]
            moved = await source.migration_manager.apply_moves(moves)
            assert moved == len(drained)

            sstats = source.migration_manager.stats
            # Grouping: one (source, target) pair chunked at batch_size=4.
            expect_bursts = -(-len(drained) // 4)  # ceil
            assert sstats.batches == expect_bursts, sstats
            assert sstats.batch_keys == len(drained)
            # Prefetch served every snapshot pre-pin, and nothing changed
            # state in between, so every handoff skipped the in-window
            # transfer: no pin-time install reached the target's inbox.
            assert sstats.prefetch_served == len(drained)
            assert sstats.prefetch_hits == len(drained)
            assert sstats.prefetch_misses == 0
            assert target.migration_manager.stats.installs == 0
            assert sstats.state_bytes > 0  # the prefetch moved real bytes
            # Pinned-window accounting covers every handoff.
            assert sstats.pinned_windows == len(drained)
            assert sstats.pinned_ms_total > 0.0
            assert (
                sstats.pinned_le_1ms
                + sstats.pinned_le_10ms
                + sstats.pinned_le_100ms
                + sstats.pinned_gt_100ms
                == len(drained)
            )
            assert not source.migration_manager._pinned

            # Every drained key serves from the target with BOTH kinds of
            # state intact — volatile arrived via the prefetch stash alone.
            for k in drained:
                out = await client.send(Counter, k, Get(), returns=Totals)
                assert out.address == target.local_address
                assert (out.total, out.hot) == (3, 3), (k, out)
            assert DOUBLE == []
        finally:
            client.close()

    async def wrapped(cluster: Cluster):
        for s in cluster.servers:
            s.app_data.set(state, as_type=StateProvider)
        await body(cluster)

    asyncio.run(
        run_integration_test(
            wrapped,
            registry_builder=build_registry,
            num_servers=2,
            server_kwargs={"migration_config": MigrationConfig(batch_size=4)},
        )
    )


# ---------------------------------------------------------------------------
# Chaos: source dies mid-migration → exactly-once reactivation from
# last persisted state
# ---------------------------------------------------------------------------


def test_source_death_mid_migration_reactivates_once_from_persisted_state():
    _reset_guards()
    state = LocalState()

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            out = await client.send(Counter, "c1", Add(amount=5), returns=Totals)
            source = next(
                s for s in cluster.servers if s.local_address == out.address
            )
            survivor = next(
                s for s in cluster.servers if s.local_address != out.address
            )

            # The transfer RPC dies mid-handoff (network partition between
            # deactivate and install): the migration must abort with the
            # managed snapshot already persisted and the directory untouched.
            async def failing_install(target, oid, payload):
                raise OSError("network partition mid-transfer")

            source.migration_manager._install_on = failing_install
            ok = await source.migration_manager.migrate_out(
                ObjectId("Counter", "c1"), survivor.local_address
            )
            assert ok is False
            assert source.migration_manager.stats.aborted == 1
            assert not source.registry.has("Counter", "c1")
            assert (
                await cluster.allocation_address("Counter", "c1")
                == source.local_address
            )

            # Now the wounded source dies outright.
            source.admin_sender().send(AdminCommand.server_exit())
            deadline = asyncio.get_event_loop().time() + 10.0
            while asyncio.get_event_loop().time() < deadline:
                if not await cluster.members.is_active(source.local_address):
                    break
                await asyncio.sleep(0.02)

            # First read re-seats on the survivor and reloads the LAST
            # PERSISTED state — exactly one reactivation, nothing doubled.
            out = await client.send(Counter, "c1", Get(), returns=Totals)
            assert out.address == survivor.local_address
            assert out.total == 5  # the pre-abort snapshot survived
            assert out.hot == 0  # volatile is gone by design: never installed
            assert ACTIVATIONS["c1"] == 2  # initial + exactly one recovery
        finally:
            client.close()

    async def wrapped(cluster: Cluster):
        for s in cluster.servers:
            s.app_data.set(state, as_type=StateProvider)
        await body(cluster)

    asyncio.run(
        run_integration_test(wrapped, registry_builder=build_registry, num_servers=2)
    )


# ---------------------------------------------------------------------------
# Chaos: source fails partway through a BATCH → the completed prefix keeps
# its flips + fences, the rest degrades to lazy re-seat, nothing stays pinned
# ---------------------------------------------------------------------------


def test_source_failure_mid_batch_leaves_no_stranded_pins():
    """A burst loses its transfer path after the first key (partition /
    source dying): the already-flipped key serves from the target behind its
    fence, every other key aborts per-key WITHOUT stranding a pin or
    touching the directory, and when the source then dies outright the
    leftover keys re-seat exactly once from persisted state."""
    _reset_guards()
    state = LocalState()
    n_objects = 6
    keys = [f"x{i}" for i in range(n_objects)]

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            owners: dict[str, list[str]] = {s.local_address: [] for s in cluster.servers}
            for k in keys:
                out = await client.send(Counter, k, Add(amount=2), returns=Totals)
                owners[out.address].append(k)
            source_addr = max(owners, key=lambda a: len(owners[a]))
            batch = owners[source_addr]
            assert len(batch) >= 2, owners  # need a prefix AND a remainder
            source = next(s for s in cluster.servers if s.local_address == source_addr)
            survivor = next(
                s for s in cluster.servers if s.local_address != source_addr
            )

            # The transfer path dies after one install (prefetch is off, so
            # every handoff must cross it; handoff_concurrency=1 makes the
            # failure point deterministic).
            real_install = source.migration_manager._install_on
            calls = {"n": 0}

            async def dying_install(target, oid, payload):
                calls["n"] += 1
                if calls["n"] > 1:
                    raise OSError("source lost its network mid-batch")
                await real_install(target, oid, payload)

            source.migration_manager._install_on = dying_install

            # The SURVIVOR coordinates: the burst travels as one
            # MigrateBatch RPC to the source's control actor.
            moves = [
                (f"Counter.{k}", source_addr, survivor.local_address) for k in batch
            ]
            moved = await survivor.migration_manager.apply_moves(moves)
            assert moved == 1  # the pre-failure prefix
            sstats = source.migration_manager.stats
            assert sstats.completed == 1
            assert sstats.aborted == len(batch) - 1
            # The safety core: nothing is left pinned, and only the
            # completed key's row flipped.
            assert not source.migration_manager._pinned
            flipped = [
                k
                for k in batch
                if await cluster.allocation_address("Counter", k)
                == survivor.local_address
            ]
            assert len(flipped) == 1
            # Its fence holds: the source refuses with a redirect rather
            # than re-activating (the epoch fence survives the failed tail).
            assert ("Counter", flipped[0]) in source.migration_manager._fenced
            out = await client.send(Counter, flipped[0], Get(), returns=Totals)
            assert out.address == survivor.local_address
            assert (out.total, out.hot) == (2, 2)

            # Failed keys re-activate on the (still live) source from
            # persisted state — volatile lost by design, nothing doubled.
            for k in batch:
                if k == flipped[0]:
                    continue
                out = await client.send(Counter, k, Get(), returns=Totals)
                assert out.address == source_addr
                assert out.total == 2
            assert DOUBLE == []

            # Now the wounded source dies outright: the leftover keys
            # re-seat on the survivor exactly once, from persisted state.
            source.admin_sender().send(AdminCommand.server_exit())
            deadline = asyncio.get_event_loop().time() + 10.0
            while asyncio.get_event_loop().time() < deadline:
                if not await cluster.members.is_active(source_addr):
                    break
                await asyncio.sleep(0.02)
            # server_exit is a HARD exit (no shutdown lifecycle): a real
            # process death takes its activations with it, but the
            # in-process guard can't see that — retire them by hand so
            # the survivor's re-seats aren't misread as doubles.
            for k, addr in list(ACTIVE.items()):
                if addr == source_addr:
                    ACTIVE.pop(k)
            for k in batch:
                out = await client.send(Counter, k, Get(), returns=Totals)
                assert out.address == survivor.local_address
                assert out.total == 2
            assert DOUBLE == []
        finally:
            client.close()

    async def wrapped(cluster: Cluster):
        for s in cluster.servers:
            s.app_data.set(state, as_type=StateProvider)
        await body(cluster)

    asyncio.run(
        run_integration_test(
            wrapped,
            registry_builder=build_registry,
            num_servers=2,
            server_kwargs={
                "migration_config": MigrationConfig(
                    prefetch=False, handoff_concurrency=1
                )
            },
        )
    )


def test_apply_moves_whole_burst_failure_degrades_safely():
    """The source is gone before the batch RPC even lands (claimed active by
    a stale membership view): the burst fails as a unit, apply_moves counts
    one abort and returns without raising — rows stand for the lazy path."""

    async def run():
        from rio_tpu.cluster.storage import Member

        members = LocalStorage()
        # Stale view: claimed active but nothing listens there.
        await members.push(Member(ip="1.1.1.1", port=1, active=True))
        mgr = MigrationManager(
            address="9.9.9.9:9",
            registry=Registry().add_type(Counter),
            placement=LocalObjectPlacement(),
            members_storage=members,
            app_data=AppData(),
            config=MigrationConfig(prefetch=False),
        )

        class _DeadClient:
            def send(self, *a, **kw):
                raise OSError("connection refused")

            def close(self):
                pass

        mgr._client = _DeadClient()
        moved = await mgr.apply_moves(
            [("Counter.a", "1.1.1.1:1", "2.2.2.2:2"),
             ("Counter.b", "1.1.1.1:1", "2.2.2.2:2")]
        )
        assert moved == 0
        assert mgr.stats.aborted == 1  # one burst, one abort
        assert not mgr._pinned

    asyncio.run(run())


# ---------------------------------------------------------------------------
# Node-scoped control plane routing
# ---------------------------------------------------------------------------


def test_node_scoped_inbox_routes_by_address():
    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            target = cluster.servers[1]
            # Sent blind through the cluster client: whichever node takes
            # the request must redirect to the id-named node, which serves.
            ack = await client.send(
                INBOX_TYPE,
                target.local_address,
                InstallState(type_name="Counter", object_id="x", payload=b"\x01"),
                returns=MigrationAck,
            )
            assert ack.ok
            assert ("Counter", "x") in target.migration_manager._stash
            # No directory row was written for the control actor.
            assert (
                await cluster.placement.lookup(
                    ObjectId(INBOX_TYPE, target.local_address)
                )
                is None
            )
        finally:
            client.close()

    asyncio.run(
        run_integration_test(body, registry_builder=build_registry, num_servers=2)
    )


# ---------------------------------------------------------------------------
# Unit: refusal/fence state machine
# ---------------------------------------------------------------------------


def _bare_manager(address="1.1.1.1:1", registry=None) -> MigrationManager:
    return MigrationManager(
        address=address,
        registry=registry or Registry(),
        placement=LocalObjectPlacement(),
        members_storage=LocalStorage(),
        app_data=AppData(),
    )


def test_pin_and_fence_refusals():
    async def run():
        mgr = _bare_manager()
        oid = ObjectId("Counter", "c1")

        assert await mgr.refusal_for(oid) is None
        assert mgr.activation_refusal(oid) is None

        mgr._pinned[("Counter", "c1")] = "2.2.2.2:2"
        err = await mgr.refusal_for(oid)
        assert err is not None and err.kind == ResponseError.deallocate().kind
        err = mgr.activation_refusal(oid)
        assert err is not None and err.kind == ResponseError.deallocate().kind
        mgr._pinned.clear()

        import time

        mgr._fenced[("Counter", "c1")] = ("2.2.2.2:2", time.monotonic())
        err = await mgr.refusal_for(oid)
        assert err is not None and err.kind == ResponseError.redirect("x").kind
        assert err.detail == "2.2.2.2:2"  # directory empty → remembered target
        err = mgr.activation_refusal(oid)
        assert err is not None and err.detail == "2.2.2.2:2"

        # The fence clears itself when the directory seats the object
        # back on this node (a later solve moved it home).
        from rio_tpu.object_placement import ObjectPlacementItem

        await mgr.placement.update(ObjectPlacementItem(oid, mgr.address))
        assert await mgr.refusal_for(oid) is None
        assert ("Counter", "c1") not in mgr._fenced
        assert mgr.stats.refusals == 4

    asyncio.run(run())


def test_split_key_prefers_longest_registered_type():
    @type_name("acme.Counter.v2")
    class Dotted(ServiceObject):
        pass

    mgr = _bare_manager(registry=Registry().add_type(Dotted))
    assert mgr._split_key("acme.Counter.v2.user.42") == ObjectId(
        "acme.Counter.v2", "user.42"
    )
    # Framework shard rows parse without being registry types.
    assert mgr._split_key(f"{SHARD_TYPE}.7") == ObjectId(SHARD_TYPE, "7")
    # Foreign rows degrade to a first-dot split; the dotless are unroutable.
    assert mgr._split_key("Other.x") == ObjectId("Other", "x")
    assert mgr._split_key("nodots") is None


def test_apply_moves_flips_dead_source_and_shard_rows():
    async def run():
        members = LocalStorage()
        await members.set_active("9.9.9.9", 9)
        placement = LocalObjectPlacement()
        mgr = MigrationManager(
            address="9.9.9.9:9",
            registry=Registry(),
            placement=placement,
            members_storage=members,
            app_data=AppData(),
        )
        from rio_tpu.object_placement import ObjectPlacementItem

        shard = ObjectId(SHARD_TYPE, "3")
        dead_obj = ObjectId("Ghost", "g1")
        await placement.update(ObjectPlacementItem(shard, "1.1.1.1:1"))
        await placement.update(ObjectPlacementItem(dead_obj, "1.1.1.1:1"))

        moved = await mgr.apply_moves(
            [
                (f"{SHARD_TYPE}.3", "1.1.1.1:1", "2.2.2.2:2"),
                ("Ghost.g1", "1.1.1.1:1", "2.2.2.2:2"),  # dead src, foreign type
                ("Ghost.g1", "3.3.3.3:3", "3.3.3.3:3"),  # src==dst: skipped
                ("nodots", "1.1.1.1:1", "2.2.2.2:2"),  # unroutable: skipped
            ]
        )
        assert moved == 2
        assert await placement.lookup(shard) == "2.2.2.2:2"
        assert await placement.lookup(dead_obj) == "2.2.2.2:2"
        assert mgr.stats.seat_flips == 2

        # A row someone already re-seated must NOT be flipped again.
        await placement.update(ObjectPlacementItem(shard, "5.5.5.5:5"))
        moved = await mgr.apply_moves([(f"{SHARD_TYPE}.3", "1.1.1.1:1", "2.2.2.2:2")])
        assert moved == 0
        assert await placement.lookup(shard) == "5.5.5.5:5"

    asyncio.run(run())


def test_migrate_out_refuses_bad_targets():
    async def run():
        mgr = _bare_manager()
        oid = ObjectId("Counter", "c1")
        assert not await mgr.migrate_out(oid, "")  # no target
        assert not await mgr.migrate_out(oid, mgr.address)  # self-move
        assert not await mgr.migrate_out(oid, "2.2.2.2:2")  # target not active
        assert mgr.stats.started == 0

        mgr._pinned[("Counter", "c1")] = "3.3.3.3:3"
        members = mgr.members_storage
        await members.set_active("2.2.2.2", 2)
        assert not await mgr.migrate_out(oid, "2.2.2.2:2")  # already pinned

    asyncio.run(run())


# ---------------------------------------------------------------------------
# Unit: registry deactivation under the dispatch lock
# ---------------------------------------------------------------------------


def test_registry_deactivate_fences_queued_dispatch():
    """A request already queued on the object lock when deactivation wins
    must surface ObjectNotFound, not run against the removed instance."""

    @message
    class Slow:
        pass

    release = asyncio.Event()
    runs: list[str] = []

    class Sleepy(ServiceObject):
        @handler
        async def slow(self, msg: Slow, ctx: AppData) -> int:
            runs.append(self.id)
            await release.wait()
            return 1

    async def run():
        reg = Registry().add_type(Sleepy)
        app = AppData()
        reg.insert("Sleepy", "s1", reg.new_from_type("Sleepy", "s1"))

        from rio_tpu import codec

        first = asyncio.create_task(
            reg.send_raw("Sleepy", "s1", "Slow", codec.serialize(Slow()), app)
        )
        await asyncio.sleep(0.01)  # first holds the lock
        # Lock waiters wake FIFO: deactivation queues ahead of the request.
        deact = asyncio.create_task(reg.deactivate("Sleepy", "s1", app))
        await asyncio.sleep(0.01)
        queued = asyncio.create_task(
            reg.send_raw("Sleepy", "s1", "Slow", codec.serialize(Slow()), app)
        )
        await asyncio.sleep(0.01)
        release.set()

        await first  # completes normally
        assert await deact is True
        with pytest.raises(ObjectNotFound):
            await queued
        assert runs == ["s1"]  # the queued request never ran a handler
        assert not reg.has("Sleepy", "s1")

        # Deactivating a non-live object reports False.
        assert await reg.deactivate("Sleepy", "s1", app) is False

    asyncio.run(run())


def test_registry_deactivate_runs_snapshot_under_lock():
    async def run():
        reg = Registry().add_type(Counter)
        app = AppData()
        obj = reg.new_from_type("Counter", "c9")
        obj.hot = 42
        reg.insert("Counter", "c9", obj)

        seen: list[int] = []

        async def snap(o):
            seen.append(o.hot)

        assert await reg.deactivate("Counter", "c9", app, before_remove=snap)
        assert seen == [42]
        assert not reg.has("Counter", "c9")

    asyncio.run(run())


# ---------------------------------------------------------------------------
# Satellite: otel gauges
# ---------------------------------------------------------------------------


def test_stats_gauges_flatten_and_exporter_gates():
    from rio_tpu.otel import otlp_metrics_exporter, stats_gauges
    from rio_tpu.placement_daemon import PlacementDaemonStats

    gauges = stats_gauges(
        placement_daemon=PlacementDaemonStats(polls=4, moves=2, bursts=1),
        migration=MigrationStats(started=3, state_bytes=128, prefetch_hits=2),
        absent=None,
    )
    assert gauges["rio.placement_daemon.polls"] == 4.0
    assert gauges["rio.placement_daemon.moves"] == 2.0
    assert gauges["rio.placement_daemon.bursts"] == 1.0
    assert gauges["rio.migration.started"] == 3.0
    assert gauges["rio.migration.state_bytes"] == 128.0
    # The batched-engine counters export like every other stats field.
    assert gauges["rio.migration.prefetch_hits"] == 2.0
    for key in ("batches", "batch_keys", "prefetch_served", "prefetch_misses",
                "pinned_windows", "pinned_ms_total", "pinned_ms_max",
                "pinned_le_1ms", "pinned_le_10ms", "pinned_le_100ms",
                "pinned_gt_100ms"):
        assert f"rio.migration.{key}" in gauges, key
    assert not any(k.startswith("rio.absent") for k in gauges)

    # The SDK-backed exporter is optional and must gate loudly without it.
    with pytest.raises(ImportError, match="opentelemetry"):
        otlp_metrics_exporter(lambda: gauges)


def test_server_gauges_cover_wired_subsystems():
    from rio_tpu.otel import server_gauges

    async def body(cluster: Cluster):
        gauges = server_gauges(cluster.servers[0])
        assert "rio.migration.started" in gauges
        assert "rio.registry.objects" in gauges

    asyncio.run(
        run_integration_test(body, registry_builder=build_registry, num_servers=1)
    )


# ---------------------------------------------------------------------------
# The coordinator under sustained churn (PR 27): one record a plan, sockets a
# process shares, nothing pulled where nothing can be
# ---------------------------------------------------------------------------


class Plain(ServiceObject):
    """No ``__migrate_state__``: a hand-off has nothing volatile to carry."""

    @handler
    async def add(self, msg: Add, ctx: AppData) -> Totals:
        return Totals()


def test_a_plan_is_one_stage_with_its_slowest_burst_and_counters():
    from rio_tpu import tracing
    from rio_tpu.object_placement import ObjectPlacementItem

    async def body(cluster: Cluster):
        a, b = cluster.servers
        for i in range(6):
            await cluster.placement.update(
                ObjectPlacementItem(ObjectId("Plain", f"p{i}"), a.local_address)
            )
        await cluster.placement.update(
            ObjectPlacementItem(ObjectId("Plain", "ghost"), "1.1.1.1:1")
        )
        tracing.clear_stages()
        moves = [(f"Plain.p{i}", a.local_address, b.local_address) for i in range(6)]
        moves.append(("Plain.ghost", "1.1.1.1:1", b.local_address))  # dead source
        mgr = b.migration_manager  # neither source nor the ghost's: a coordinator
        assert await mgr.apply_moves(moves) == 7
        for i in range(6):
            assert await cluster.placement.lookup(ObjectId("Plain", f"p{i}")) == b.local_address
        st = mgr.stats
        assert (st.plans, st.plan_bursts, st.plan_burst_keys, st.plan_flips) == (1, 1, 6, 1)
        assert st.plan_burst_ms_max > 0.0
        # The source ran the moves though it held no activation: pins, fences.
        assert a.migration_manager.stats.seat_flips == 6
        log = tracing.stage_log()
        plan = [r for r in log if r[0] == "migrate.apply_moves"]
        burst = [r for r in log if r[0] == "migrate.burst"]
        flips = [r for r in log if r[0] == "migrate.flips"]
        assert len(plan) == len(burst) == len(flips) == 1  # one record a plan, not a burst
        assert burst[0][3] == flips[0][3] == "migrate.apply_moves"
        assert plan[0][1] <= burst[0][1] <= burst[0][2] <= plan[0][2]

    asyncio.run(run_integration_test(
        body, registry_builder=lambda: Registry().add_type(Plain), num_servers=2,
    ))


def test_sorting_a_plan_is_a_stage_that_leaves_the_membership_read_out():
    """``migrate.plan`` times the loop's own pass over the moves; the one
    membership read in its middle is a wait (a remote store: tens of ms in
    which other code runs) and lies between its two records, in neither."""
    from rio_tpu import tracing
    from rio_tpu.object_placement import ObjectPlacementItem

    async def body(cluster: Cluster):
        a, b = cluster.servers
        for i in range(6):
            await cluster.placement.update(
                ObjectPlacementItem(ObjectId("Plain", f"p{i}"), a.local_address)
            )
        mgr = b.migration_manager
        real = mgr.members_storage.active_members
        read = []

        async def slow_read():
            t0 = time.perf_counter_ns()
            await asyncio.sleep(0.05)
            read.append((t0, time.perf_counter_ns()))
            return await real()

        mgr.members_storage.active_members = slow_read
        tracing.clear_stages()
        try:
            moves = [(f"Plain.p{i}", a.local_address, b.local_address) for i in range(6)]
            assert await mgr.apply_moves(moves) == 6
        finally:
            mgr.members_storage.active_members = real
        log = tracing.stage_log()
        plans = [r for r in log if r[0] == "migrate.plan"]
        assert len(read) == 1 and len(plans) == 2  # before the read, after it
        assert plans[0][2] <= read[0][0] and read[0][1] <= plans[1][1]
        assert all(r[3] == "migrate.apply_moves" and not r[6] for r in plans)
        assert all(r[2] - r[1] < 40e6 for r in plans)
        # The slowest burst's span is a wait and says so; the plan's root is work.
        (burst,) = [r for r in log if r[0] == "migrate.burst"]
        (root,) = [r for r in log if r[0] == "migrate.apply_moves"]
        assert burst[6] is True and root[6] is False

    asyncio.run(run_integration_test(
        body, registry_builder=lambda: Registry().add_type(Plain), num_servers=2,
    ))


def test_nothing_is_prefetched_for_a_type_without_volatile_state():
    async def body(cluster: Cluster):
        from rio_tpu.object_placement import ObjectPlacementItem

        a, b = cluster.servers
        for tname, n in (("Plain", 3), ("Counter", 3)):
            for i in range(n):
                await cluster.placement.update(
                    ObjectPlacementItem(ObjectId(tname, f"x{i}"), a.local_address)
                )
        mgr = b.migration_manager
        pulls = []
        real = type(mgr).prefetch_pull

        async def spy(self, source, items):
            pulls.append([t for t, _ in items])
            return await real(self, source, items)

        type(mgr).prefetch_pull = spy
        try:
            plain = [(f"Plain.x{i}", a.local_address, b.local_address) for i in range(3)]
            assert await mgr.apply_moves(plain) == 3
            assert pulls == []  # two calls a burst saved: nothing to warm
            held = [(f"Counter.x{i}", a.local_address, b.local_address) for i in range(3)]
            assert await mgr.apply_moves(held) == 3
            assert pulls == [["Counter"] * 3]
        finally:
            type(mgr).prefetch_pull = real
        assert a.registry.exports_volatile("Counter") and not a.registry.exports_volatile("Plain")
        assert a.registry.exports_volatile("NeverHeardOf")  # unknown: it may

    asyncio.run(run_integration_test(
        body, registry_builder=lambda: build_registry().add_type(Plain), num_servers=2,
    ))


def test_one_sources_small_bursts_to_many_targets_travel_in_one_call():
    """A re-priced node sheds a row or two to each of many targets. A call a
    (source, target) pair is a round trip and a membership read each, two at
    a time a source (``per_node_inflight``): the hand-offs of one derate step
    at 1,024 members held the loop for seconds, which stepped the derate
    again. They travel in ONE ``MigrateSpread`` a source (up to ``batch_size``
    keys); a full burst still travels alone, as a ``MigrateBatch``."""
    from rio_tpu.migration import MigrateBatch, MigrateSpread
    from rio_tpu.object_placement import ObjectPlacementItem

    async def body(cluster: Cluster):
        a, b, c, d, coord = cluster.servers
        targets = [b.local_address, c.local_address, d.local_address]
        for i in range(6):
            await cluster.placement.update(
                ObjectPlacementItem(ObjectId("Plain", f"s{i}"), a.local_address)
            )
        for i in range(4):
            await cluster.placement.update(
                ObjectPlacementItem(ObjectId("Plain", f"f{i}"), b.local_address)
            )
        mgr = coord.migration_manager  # batch_size 4 (the helper's config)
        sent = []
        control = mgr._get_client("control")
        real = control.send

        async def spy(type_name, object_id, msg, **kw):
            sent.append((type(msg), object_id))
            return await real(type_name, object_id, msg, **kw)

        control.send = spy
        membership_reads = []
        storage = a.migration_manager.members_storage
        real_active, real_is = storage.active_members, storage.is_active

        async def active_members():
            membership_reads.append("all")
            return await real_active()

        async def is_active(address):
            membership_reads.append(address)
            return await real_is(address)

        storage.active_members, storage.is_active = active_members, is_active
        try:
            # Two rows to each of three targets from a; a full burst from b.
            moves = [(f"Plain.s{i}", a.local_address, targets[i % 3]) for i in range(6)]
            moves += [(f"Plain.f{i}", b.local_address, c.local_address) for i in range(4)]
            assert await mgr.apply_moves(moves) == 10
        finally:
            control.send = real
            del storage.active_members, storage.is_active
        for i in range(6):
            assert await cluster.placement.lookup(ObjectId("Plain", f"s{i}")) == targets[i % 3]
        st = mgr.stats
        assert (st.plan_bursts, st.plan_burst_keys) == (4, 10)  # bursts are pairs, as ever
        # a's three bursts (6 keys > batch_size 4): a spread of two and a
        # burst; b's full burst alone.
        assert sorted((t.__name__, to) for t, to in sent) == sorted([
            ("MigrateSpread", a.local_address), ("MigrateBatch", a.local_address),
            ("MigrateBatch", b.local_address),
        ])
        assert a.migration_manager.stats.batches == 3 and a.migration_manager.stats.batch_keys == 6
        # The coordinator's one read a plan; on the sources one read a call: the
        # whole table for the spread, the one row for a burst.
        assert membership_reads.count("all") == 2 and len(membership_reads) == 4
        assert set(membership_reads) - {"all"} <= set(targets)
        # A target that left: its burst is refused, the others of the spread run.
        for i in range(6):
            await cluster.placement.update(
                ObjectPlacementItem(ObjectId("Plain", f"s{i}"), a.local_address)
            )
        host, _, port = d.local_address.rpartition(":")
        await storage.set_inactive(host, int(port))
        done, attempted = await a.migration_manager.migrate_spread(
            [[b.local_address, [["Plain", "s0"]]], [d.local_address, [["Plain", "s1"]]],
             [c.local_address, [["Plain", "s2"]]]]
        )
        assert (done, attempted) == (2, 3)
        assert await cluster.placement.lookup(ObjectId("Plain", "s1")) == a.local_address

    asyncio.run(run_integration_test(
        body, registry_builder=lambda: Registry().add_type(Plain), num_servers=5,
        server_kwargs={"migration_config": MigrationConfig(batch_size=4)},
    ))


def test_fences_are_pruned_from_the_front_of_their_order():
    from rio_tpu import migration

    mgr = MigrationManager(
        address="9.9.9.9:9", registry=Registry(), placement=LocalObjectPlacement(),
        members_storage=LocalStorage(), app_data=AppData(),
    )
    now = time.monotonic()
    old = now - migration.FENCE_TTL - 1
    mgr._fenced = {("T", "a"): ("x", old), ("T", "b"): ("x", old), ("T", "c"): ("x", now)}
    mgr._prune_fences()
    assert list(mgr._fenced) == [("T", "c")]


def test_managers_of_one_process_share_their_sockets_by_lane():
    from rio_tpu.migration import open_connections
    from rio_tpu.otel import server_gauges

    async def body(cluster: Cluster):
        a, b, c = cluster.servers
        ma, mb = a.migration_manager, b.migration_manager
        control, inbox = ma._get_client("control"), ma._get_client("inbox")
        assert control is not inbox and control._conns is not inbox._conns
        assert mb._get_client("control") is not control
        assert mb._get_client("control")._conns is control._conns  # the process's
        assert mb._get_client("inbox")._conns is inbox._conns
        # Both dial c's inbox: one bundle, one socket.
        from rio_tpu.migration import INBOX_TYPE, InstallState, MigrationAck

        for mgr in (ma, mb, ma, mb):
            ack = await mgr._get_client("inbox").send(
                INBOX_TYPE, c.local_address,
                InstallState(type_name="Counter", object_id="k", payload=b"\x80"),
                returns=MigrationAck,
            )
            assert ack.ok
        assert open_connections() == 1
        assert server_gauges(a)["rio.migrate.open_connections"] == 1.0
        # The first to close leaves the lane to the other; the last closes it.
        ma.close()
        assert open_connections() == 1
        mb.close()
        assert open_connections() == 0

    asyncio.run(run_integration_test(body, registry_builder=build_registry, num_servers=3))


def test_bare_flips_give_the_loop_a_turn():
    """Thousands of uncontended ``update()`` calls never suspend: the
    coordinator yields between runs of them so requests keep being served."""

    async def run():
        from rio_tpu.migration import _FLIPS_PER_TURN
        from rio_tpu.object_placement import ObjectPlacementItem

        members = LocalStorage()
        placement = LocalObjectPlacement()
        mgr = MigrationManager(
            address="9.9.9.9:9", registry=Registry(), placement=placement,
            members_storage=members, app_data=AppData(),
        )
        n = 4 * _FLIPS_PER_TURN
        for i in range(n):
            await placement.update(ObjectPlacementItem(ObjectId("Ghost", str(i)), "1.1.1.1:1"))
        turns = 0

        async def other_work():
            nonlocal turns
            while True:
                turns += 1
                await asyncio.sleep(0)

        task = asyncio.create_task(other_work())
        moved = await mgr.apply_moves(
            [(f"Ghost.{i}", "1.1.1.1:1", "2.2.2.2:2") for i in range(n)]
        )
        task.cancel()
        assert moved == n and mgr.stats.plan_flips == n
        assert turns >= 4

    asyncio.run(run())


def test_a_burst_through_a_member_that_left_fails_at_once():
    """Between a plan's snapshot and its burst the source may leave. Its
    peers answer for it with DEALLOCATE; twenty re-tries of that (a second
    and more, the client's default) would hold the whole plan, and with it
    the next event's solve. The lanes give up after a few."""
    import time

    async def body(cluster: Cluster):
        from rio_tpu.object_placement import ObjectPlacementItem

        a, b, c = cluster.servers
        gone = a.local_address
        for i in range(3):
            await cluster.placement.update(
                ObjectPlacementItem(ObjectId("Plain", f"p{i}"), gone)
            )
        # The membership view the coordinator reads still calls it active
        # (the plan was made a moment ago); the node itself is gone.
        cluster.tasks[0].cancel()
        await asyncio.gather(cluster.tasks[0], return_exceptions=True)
        host, _, port = gone.rpartition(":")
        real = type(cluster.members).active_members

        async def stale(self):
            from rio_tpu.cluster.storage import Member

            return [*await real(self), Member.from_address(gone, active=True)]

        type(cluster.members).active_members = stale
        try:
            t0 = time.perf_counter()
            moved = await c.migration_manager.apply_moves(
                [(f"Plain.p{i}", gone, b.local_address) for i in range(3)]
            )
            took = time.perf_counter() - t0
        finally:
            type(cluster.members).active_members = real
        assert moved == 0 and c.migration_manager.stats.aborted == 1
        assert took < 0.5, took
        for i in range(3):  # the rows stand for the next solve
            assert await cluster.placement.lookup(ObjectId("Plain", f"p{i}")) == gone

    asyncio.run(run_integration_test(
        body, registry_builder=lambda: Registry().add_type(Plain), num_servers=3,
    ))
