"""Churn-driven placement: the Server-owned daemon re-solves with ZERO app code.

r2 review #3 / SURVEY §7.3: the reference recovers lazily inside the
request path (``rio-rs/src/service.rs:227-298``); rio-tpu additionally
re-seats displaced objects *proactively* — gossip marks a node dead, the
``PlacementDaemon`` feeds liveness to ``JaxObjectPlacement.sync_members``
and triggers a warm-started ``rebalance()``, and traffic finds every object
already re-placed.  The application never touches the solver.
"""

import asyncio
import time

from rio_tpu import AppData, LocalObjectPlacement, LocalStorage, Registry, ServiceObject, handler, message
from rio_tpu.commands import AdminCommand, ServerInfo
from rio_tpu.object_placement.jax_placement import AffinityTracker, JaxObjectPlacement
from rio_tpu.placement_daemon import PlacementDaemon, PlacementDaemonConfig

from .server_utils import Cluster, run_integration_test

N_OBJECTS = 96


@message
class Poke:
    pass


@message
class Where:
    address: str = ""


class Pin(ServiceObject):
    @handler
    async def poke(self, msg: Poke, ctx: AppData) -> Where:
        return Where(address=ctx.get(ServerInfo).address)

def build_registry() -> Registry:
    return Registry().add_type(Pin)


def test_daemon_reseats_displaced_objects_without_app_solver_calls():
    """Kill a node; the daemon alone re-places its objects (≈ displaced share)."""
    placement = JaxObjectPlacement(mode="greedy", move_cost=0.5)

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            # Allocate a population across 3 nodes.
            for i in range(N_OBJECTS):
                await client.send(Pin, f"o{i}", Poke(), returns=Where)
            assert placement.count() == N_OBJECTS

            placed_before = {
                f"o{i}": await cluster.allocation_address("Pin", f"o{i}")
                for i in range(N_OBJECTS)
            }
            victim = max(
                cluster.addresses, key=lambda a: sum(1 for v in placed_before.values() if v == a)
            )
            displaced = [k for k, v in placed_before.items() if v == victim]
            assert displaced, "victim hosted nothing; test setup broken"

            # Kill the victim node via its admin channel (deterministic —
            # a wire-level kill could be retried onto a survivor).
            victim_server = next(
                s for s in cluster.servers if s.local_address == victim
            )
            victim_server.admin_sender().send(AdminCommand.server_exit())

            # Wait for the DAEMON (not the test, not the app) to re-solve.
            daemons = [
                s.placement_daemon
                for s in cluster.servers
                if getattr(s, "placement_daemon", None) is not None
            ]
            assert daemons, "placement daemon was not started"
            deadline = asyncio.get_event_loop().time() + 15.0
            while asyncio.get_event_loop().time() < deadline:
                if any(d.stats.rebalances > 0 for d in daemons):
                    break
                await asyncio.sleep(0.05)
            else:
                raise TimeoutError("daemon never rebalanced after node death")

            # Every displaced object now has a LIVE owner in the directory —
            # proactively, before any traffic touched it.
            live = set(cluster.addresses) - {victim}
            reseated = 0
            deadline = asyncio.get_event_loop().time() + 10.0
            while asyncio.get_event_loop().time() < deadline:
                addrs = [
                    await cluster.allocation_address("Pin", k) for k in displaced
                ]
                reseated = sum(1 for a in addrs if a in live)
                if reseated == len(displaced):
                    break
                await asyncio.sleep(0.05)
            assert reseated == len(displaced), (
                f"{len(displaced) - reseated} displaced objects still "
                f"point at the dead node"
            )

            # Churn moved ≈ the displaced share, not a global reshuffle.
            moved_total = sum(d.stats.moves for d in daemons)
            assert moved_total >= len(displaced)
            assert moved_total <= len(displaced) + N_OBJECTS // 4, (
                f"daemon moved {moved_total} objects for {len(displaced)} displaced"
            )

            # And traffic is served from live nodes with no app solver call.
            for k in displaced[:8]:
                out = await client.send(Pin, k, Poke(), returns=Where)
                assert out.address in live
        finally:
            client.close()

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=build_registry,
            num_servers=3,
            placement=placement,
            gossip=True,
            timeout=60.0,
            server_kwargs={
                "placement_daemon": True,
                "placement_daemon_config": PlacementDaemonConfig(
                    poll_interval=0.1, debounce=0.05, min_rebalance_interval=0.1
                ),
            },
        )
    )


def test_drain_flow_on_live_cluster():
    """The ops drain story end to end: cordon -> rebalance re-seats exactly
    the drained node's population -> traffic lands on survivors -> stop
    the server with nothing displaced (vs the reference's only exit:
    death + lazy re-allocation)."""
    placement = JaxObjectPlacement(mode="greedy", move_cost=0.5)

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            for i in range(90):
                await client.send(Pin, f"o{i}", Poke(), returns=Where)

            async def seat(k):
                return await cluster.allocation_address("Pin", k)

            seats = {f"o{i}": await seat(f"o{i}") for i in range(90)}
            victim = max(
                cluster.addresses,
                key=lambda a: sum(1 for v in seats.values() if v == a),
            )
            on_victim = [k for k, v in seats.items() if v == victim]

            placement.cordon(victim)
            moved = await placement.rebalance()
            # ~Exactly the drained population moves (stay-put discount;
            # +small slack for integer-quota repair ties).
            assert on_victim and len(on_victim) <= moved <= len(on_victim) + 5, (
                moved, len(on_victim),
            )
            for k in on_victim:
                assert await seat(k) != victim
            for k in on_victim[:10]:
                out = await client.send(Pin, k, Poke(), returns=Where)
                assert out.address != victim

            # Stopping the drained server displaces nothing.
            next(
                s for s in cluster.servers if s.local_address == victim
            ).admin_sender().send(AdminCommand.server_exit())
            await asyncio.sleep(0.3)
            for k in on_victim[:10]:
                out = await client.send(Pin, k, Poke(), returns=Where)
                assert out.address != victim
        finally:
            client.close()

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=build_registry,
            num_servers=3,
            placement=placement,
            timeout=60.0,
        )
    )


def test_admin_drain_command_full_flow():
    """AdminCommand.drain(): one admin message = cordon + re-solve +
    before_shutdown hooks for local instances + exit; re-seated rows are
    NEVER deleted (only rows still pointing at the draining node are)."""
    placement = JaxObjectPlacement(mode="greedy", move_cost=0.5)

    shutdowns: list[str] = []

    class DrainPin(ServiceObject):
        @handler
        async def poke(self, msg: Poke, ctx: AppData) -> Where:
            return Where(address=ctx.get(ServerInfo).address)

        async def before_shutdown(self, ctx: AppData) -> None:
            shutdowns.append(self.id)

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            for i in range(60):
                await client.send(DrainPin, f"o{i}", Poke(), returns=Where)

            seats = {
                f"o{i}": await cluster.allocation_address("DrainPin", f"o{i}")
                for i in range(60)
            }
            victim = max(
                cluster.addresses,
                key=lambda a: sum(1 for v in seats.values() if v == a),
            )
            on_victim = [k for k, v in seats.items() if v == victim]
            victim_server = next(
                s for s in cluster.servers if s.local_address == victim
            )
            victim_server.admin_sender().send(AdminCommand.drain())

            # The server exits on its own once drained...
            deadline = asyncio.get_event_loop().time() + 15.0
            while asyncio.get_event_loop().time() < deadline:
                if victim_server._stopped.is_set():
                    break
                await asyncio.sleep(0.05)
            assert victim_server._stopped.is_set(), "drain never completed"
            # ...having run before_shutdown for ITS local instances...
            assert set(shutdowns) >= set(on_victim), (
                sorted(set(on_victim) - set(shutdowns))
            )
            # ...and the full population still resolves: re-seated rows
            # survived the lifecycle cleanup (nothing was over-deleted).
            for k, old in seats.items():
                addr = await cluster.allocation_address("DrainPin", k)
                assert addr is not None and addr != victim, (k, addr)
            for k in on_victim[:8]:
                out = await client.send(DrainPin, k, Poke(), returns=Where)
                assert out.address != victim
        finally:
            client.close()

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=lambda: Registry().add_type(DrainPin),
            num_servers=3,
            placement=placement,
            timeout=60.0,
        )
    )


def test_draining_node_refuses_new_activations_but_serves_seated():
    """The quiesce gate behind drain: with the flag up, a node keeps
    serving objects already activated on it, but NEW objects bounce
    (deallocate -> client retry) and land on the other node."""
    placement = JaxObjectPlacement(mode="greedy")

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            seeded = []
            for i in range(24):
                out = await client.send(Pin, f"s{i}", Poke(), returns=Where)
                seeded.append((f"s{i}", out.address))
            draining = cluster.servers[0]
            draining._draining.active = True
            # Seated objects on the draining node still serve...
            for k, addr in seeded:
                out = await client.send(Pin, k, Poke(), returns=Where)
                assert out.address == addr, (k, out.address, addr)
            # ...but every NEW object lands on the OTHER node.
            for i in range(24):
                out = await client.send(Pin, f"n{i}", Poke(), returns=Where)
                assert out.address != draining.local_address, f"n{i}"
        finally:
            client.close()

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=build_registry,
            num_servers=2,
            placement=placement,
            timeout=60.0,
        )
    )


def test_daemon_noop_for_plain_providers():
    """Enabling the daemon with a CRUD-only provider must be harmless."""

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            out = await client.send(Pin, "x", Poke(), returns=Where)
            assert out.address in cluster.addresses
            daemon = cluster.servers[0].placement_daemon
            assert daemon is not None and not daemon.supported
        finally:
            client.close()

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=build_registry,
            num_servers=2,
            server_kwargs={"placement_daemon": True},
        )
    )


def test_dispatch_observe_feeds_affinity_tracker():
    """Served requests update the tracker with zero application wiring."""
    tracker = AffinityTracker()
    placement = JaxObjectPlacement(mode="hierarchical", affinity_tracker=tracker)

    async def body(cluster: Cluster):
        client = cluster.client()
        try:
            for i in range(8):
                await client.send(Pin, f"t{i}", Poke(), returns=Where)
            # The tracker saw every object, keyed exactly like the directory.
            assert len(tracker._obj) == 8
            for i in range(8):
                assert f"Pin.t{i}" in tracker._obj
        finally:
            client.close()

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=build_registry,
            num_servers=2,
            placement=placement,
        )
    )


def test_shared_config_stats_isolated_per_daemon():
    """Servers sharing one config object must not share stats counters."""
    cfg = PlacementDaemonConfig()
    members, placement = LocalStorage(), LocalObjectPlacement()
    d1 = PlacementDaemon(members, placement, cfg)
    d2 = PlacementDaemon(members, placement, cfg)
    assert d1.stats is not d2.stats
    assert not d1.supported  # CRUD-only provider: daemon parks


def test_daemon_retries_after_epoch_discarded_solve():
    """A rebalance that loses the epoch race (stats.discarded) must be
    retried on the next poll — the churn event is still unserved — and a
    discarded attempt must never satisfy the sibling-skip epoch check."""
    from dataclasses import dataclass, field

    @dataclass
    class FakeStats:
        epoch: int = 0
        discarded: bool = False
        history: list = field(default_factory=list)

    class FlakyPlacement:
        """First rebalance is epoch-discarded; second commits."""

        def __init__(self):
            self.stats = FakeStats()
            self.rebalances = 0

        def sync_members(self, members):
            pass

        async def rebalance(self, *, mode=None):
            self.rebalances += 1
            prior = self.stats
            if self.rebalances == 1:
                archived = (
                    prior.history
                    + [FakeStats(epoch=prior.epoch, discarded=prior.discarded)]
                    if prior.epoch
                    else []
                )
                self.stats = FakeStats(
                    epoch=prior.epoch + 1, discarded=True, history=archived
                )
                return 0
            self.stats = FakeStats(epoch=prior.epoch + 1)
            return 7

    async def run():
        storage = LocalStorage()
        placement = FlakyPlacement()
        daemon = PlacementDaemon(
            storage,
            placement,
            PlacementDaemonConfig(
                poll_interval=0.05, debounce=0.01, min_rebalance_interval=0.0
            ),
        )
        from rio_tpu.cluster.storage import Member

        await storage.push(Member.from_address("10.3.0.1:90", active=True))
        await storage.push(Member.from_address("10.3.0.2:90", active=True))
        task = asyncio.create_task(daemon.run())
        try:
            await asyncio.sleep(0.2)  # first sync (no solve)
            # Churn: one node dies.
            await storage.set_inactive("10.3.0.2", 90)
            for _ in range(100):
                if daemon.stats.rebalances >= 1:
                    break
                await asyncio.sleep(0.05)
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
        # The discarded attempt was recorded AND retried to completion.
        assert daemon.stats.rebalances_discarded == 1
        assert daemon.stats.rebalances == 1
        assert daemon.stats.moves == 7
        assert placement.rebalances == 2
        # One churn event, even though it took two attempts.
        assert daemon.stats.liveness_changes == 1

    asyncio.run(asyncio.wait_for(run(), 30))


def test_a_solve_discarded_by_another_commit_is_not_retried():
    """A daemon's solve can still lose the epoch race to a commit that is
    not a sibling daemon's (an operator's ``rebalance``, a drain). A solve
    that commits after our sync_members saw at least our liveness, so the
    event IS served: no retry ladder of no-op solves (each an epoch bump
    that discards whatever else is in flight)."""
    from rio_tpu import ObjectId
    from rio_tpu.cluster.storage import Member

    async def run():
        storage = LocalStorage()
        nodes = [f"10.5.0.{i}:90" for i in range(1, 7)]
        for a in nodes:
            await storage.push(Member.from_address(a, active=True))
        placement = JaxObjectPlacement(mode="greedy")
        placement.sync_members(await storage.members())
        await placement.assign_batch([ObjectId("T", str(i)) for i in range(120)])
        await placement.rebalance(delta=False)  # the plan deltas run against
        cfg = PlacementDaemonConfig(
            poll_interval=0.05, debounce=0.01, min_rebalance_interval=0.2
        )
        daemon = PlacementDaemon(storage, placement, cfg)
        inside = []
        real = placement.rebalance

        async def raced_rebalance(**kw):
            # The other caller snapshots the same epoch and commits first.
            inside.append(1)
            other = asyncio.ensure_future(real())
            await asyncio.sleep(0)
            moved = await real(**kw)
            await other
            return moved

        placement.rebalance = raced_rebalance
        task = asyncio.create_task(daemon.run())
        try:
            await asyncio.sleep(0.3)  # first sync (no solve)
            await storage.set_inactive("10.5.0.6", 90)
            for _ in range(200):
                if daemon.stats.rebalances + daemon.stats.rebalances_discarded:
                    break
                await asyncio.sleep(0.05)
            await asyncio.sleep(1.0)  # several rungs of the old ladder
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
        assert (daemon.stats.rebalances, daemon.stats.rebalances_discarded) == (0, 1)
        assert len(inside) == 1, "a served event was retried"
        assert not daemon._retry_solve
        seats = await placement.lookup_batch(
            [ObjectId("T", str(i)) for i in range(120)]
        )
        assert "10.5.0.6:90" not in seats

    asyncio.run(asyncio.wait_for(run(), 60))


def test_eight_daemons_on_one_provider_answer_one_event_with_one_solve():
    """N co-located servers share one provider, so one churn event wakes N
    daemons. One dispatches; the others see its solve in flight for the
    liveness they read, wait, and find the event served: one commit, at most
    one discard, nothing retried, and the device ran one solve, not eight."""
    from rio_tpu import ObjectId, tracing
    from rio_tpu.cluster.storage import Member

    async def run():
        storage = LocalStorage()
        nodes = [f"10.5.0.{i}:90" for i in range(1, 7)]
        for a in nodes:
            await storage.push(Member.from_address(a, active=True))
        placement = JaxObjectPlacement(mode="greedy")
        placement.sync_members(await storage.members())
        await placement.assign_batch([ObjectId("T", str(i)) for i in range(120)])
        await placement.rebalance(delta=False)
        cfg = PlacementDaemonConfig(
            poll_interval=0.05, debounce=0.01, min_rebalance_interval=0.2
        )
        daemons = [PlacementDaemon(storage, placement, cfg) for _ in range(8)]
        dispatched = []
        real = placement.rebalance

        async def slow_rebalance(**kw):
            # Long enough for every sibling to arrive while it is in flight.
            dispatched.append(1)
            await asyncio.sleep(0.3)
            return await real(**kw)

        placement.rebalance = slow_rebalance
        tracing.clear_stages()
        tasks = [asyncio.create_task(d.run()) for d in daemons]
        try:
            await asyncio.sleep(0.3)  # first sync (no solve)
            await storage.set_inactive("10.5.0.6", 90)
            for _ in range(200):
                if sum(d.stats.rebalances + d.stats.rebalances_skipped
                       for d in daemons) >= len(daemons):
                    break
                await asyncio.sleep(0.05)
            await asyncio.sleep(1.0)  # several rungs of the retry ladder
        finally:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        assert sum(d.stats.rebalances for d in daemons) == 1
        assert sum(d.stats.rebalances_discarded for d in daemons) <= 1
        assert sum(d.stats.rebalances_skipped for d in daemons) >= 6
        assert len(dispatched) <= 2, "siblings dispatched beside a solve in flight"
        assert not any(d._retry_solve for d in daemons)
        # The dispatcher logged how long the event waited for its solve.
        waits = [r for r in tracing.stage_log() if r[0] == "daemon.wait"]
        assert len(waits) == len(dispatched)
        assert all(0 < r[2] - r[1] < 5e9 for r in waits)
        seats = await placement.lookup_batch(
            [ObjectId("T", str(i)) for i in range(120)]
        )
        assert "10.5.0.6:90" not in seats

    asyncio.run(asyncio.wait_for(run(), 60))


def test_the_poll_that_sees_a_change_is_a_stage_from_the_tables_arrival():
    """``daemon.liveness`` is the loop's own work of the poll that saw the
    event (fingerprint, ``sync_load``, ``sync_members``, journal): stamped
    when the table has arrived, so the wait for a slow store is not in it;
    ``daemon.wait`` is a wait and says so."""
    from rio_tpu import ObjectId, tracing
    from rio_tpu.cluster.storage import Member

    async def run():
        storage = LocalStorage()
        for i in range(1, 5):
            await storage.push(Member.from_address(f"10.6.0.{i}:90", active=True))
        placement = JaxObjectPlacement(mode="greedy")
        placement.sync_members(await storage.members())
        await placement.assign_batch([ObjectId("T", str(i)) for i in range(40)])
        await placement.rebalance(delta=False)
        real = storage.members
        reads = []

        async def slow_members():
            t0 = time.perf_counter_ns()
            await asyncio.sleep(0.03)
            reads.append((t0, time.perf_counter_ns()))
            return await real()

        storage.members = slow_members
        daemon = PlacementDaemon(storage, placement, PlacementDaemonConfig(
            poll_interval=0.05, debounce=0.01, min_rebalance_interval=0.05))
        tracing.clear_stages()
        task = asyncio.create_task(daemon.run())
        try:
            await asyncio.sleep(0.3)  # first sync (no solve, no record)
            await storage.set_inactive("10.6.0.4", 90)
            for _ in range(200):
                if daemon.stats.rebalances:
                    break
                await asyncio.sleep(0.05)
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
        assert daemon.stats.liveness_changes == 1 and daemon.stats.rebalances == 1
        (seen,) = [r for r in tracing.stage_log() if r[0] == "daemon.liveness"]
        assert seen[6] is False and 0 < seen[2] - seen[1] < 25e6  # not the 30 ms read
        assert any(t1 <= seen[1] and seen[1] - t1 < 5e6 for _, t1 in reads)
        assert not any(seen[1] < t1 and t0 < seen[2] for t0, t1 in reads)
        (waited,) = [r for r in tracing.stage_log() if r[0] == "daemon.wait"]
        assert waited[6] is True

    asyncio.run(asyncio.wait_for(run(), 60))


def test_daemon_abandons_retries_after_consecutive_discards():
    """Sustained epoch races must not livelock the device: after
    max_discard_retries consecutive discards the daemon stops dispatching
    solves until the NEXT liveness change, which re-arms it."""
    from dataclasses import dataclass, field

    @dataclass
    class FakeStats:
        epoch: int = 0
        discarded: bool = False
        history: list = field(default_factory=list)

    class AlwaysDiscarded:
        """Every rebalance loses the epoch race (e.g. allocation traffic)."""

        def __init__(self):
            self.stats = FakeStats()
            self.rebalances = 0

        def sync_members(self, members):
            pass

        async def rebalance(self, *, mode=None):
            self.rebalances += 1
            self.stats = FakeStats(epoch=self.stats.epoch + 1, discarded=True)
            return 0

    async def run():
        storage = LocalStorage()
        placement = AlwaysDiscarded()
        daemon = PlacementDaemon(
            storage,
            placement,
            PlacementDaemonConfig(
                poll_interval=0.02,
                debounce=0.01,
                min_rebalance_interval=0.0,  # zero backoff: tests the CAP
                max_discard_retries=2,
            ),
        )
        from rio_tpu.cluster.storage import Member

        await storage.push(Member.from_address("10.4.0.1:90", active=True))
        await storage.push(Member.from_address("10.4.0.2:90", active=True))
        await storage.push(Member.from_address("10.4.0.3:90", active=True))
        task = asyncio.create_task(daemon.run())
        try:
            await asyncio.sleep(0.2)  # first sync (no solve)
            await storage.set_inactive("10.4.0.2", 90)
            for _ in range(100):
                if daemon.stats.retries_abandoned >= 1:
                    break
                await asyncio.sleep(0.05)
            assert daemon.stats.retries_abandoned == 1
            # Initial attempt + exactly max_discard_retries retries, then
            # silence: no further solves while liveness is stable.
            assert placement.rebalances == 3
            await asyncio.sleep(0.3)
            assert placement.rebalances == 3, "daemon kept solving after giving up"
            # A NEW churn event re-arms the daemon (and resets the ladder).
            await storage.set_inactive("10.4.0.3", 90)
            for _ in range(100):
                if placement.rebalances > 3:
                    break
                await asyncio.sleep(0.05)
            assert placement.rebalances > 3, "new churn did not re-arm the daemon"
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
        assert daemon.stats.rebalances == 0  # every attempt was discarded
        assert daemon.stats.rebalances_discarded >= 3

    asyncio.run(asyncio.wait_for(run(), 30))


def test_event_kick_wakes_loop_before_poll_interval():
    """A provider churn event (cordon here; gossip liveness flips and
    clean_server fire the same listener) must wake the daemon NOW — with a
    deliberately enormous poll_interval the re-solve can only have come
    from the kick, and with a committed plan it lands as a delta."""

    async def run():
        from rio_tpu import ObjectId
        from rio_tpu.cluster.storage import Member

        addrs = [f"10.5.0.{i}:90" for i in range(4)]
        storage = LocalStorage()
        for a in addrs:
            await storage.push(Member.from_address(a, active=True))
        placement = JaxObjectPlacement(mode="greedy", node_axis_size=4)
        placement.sync_members(await storage.members())
        await placement.assign_batch([ObjectId("K", str(i)) for i in range(64)])
        await placement.rebalance(delta=False)  # commit the PlanState
        daemon = PlacementDaemon(
            storage,
            placement,
            PlacementDaemonConfig(
                poll_interval=60.0,  # the kick, not the poll, must wake us
                debounce=0.01,
                min_rebalance_interval=0.0,
            ),
        )
        task = asyncio.create_task(daemon.run())
        try:
            for _ in range(200):
                if daemon.stats.polls >= 1:
                    break
                await asyncio.sleep(0.01)
            assert daemon.stats.polls >= 1
            # Churn: storage learns the death; the provider-side cordon
            # fires the churn listener that wakes the sleeping loop.
            await storage.set_inactive("10.5.0.0", 90)
            placement.cordon(addrs[0])
            for _ in range(200):
                if daemon.stats.rebalances >= 1:
                    break
                await asyncio.sleep(0.05)
            assert daemon.stats.rebalances >= 1, "kick did not wake the loop"
            assert daemon.stats.kicks >= 1
            assert daemon.stats.delta_rebalances >= 1
            assert placement.stats.mode == "greedy+delta"
            # The displaced objects were re-seated off the dead node.
            dead_idx = placement._nodes[addrs[0]].index
            assert len(placement._by_node.get(dead_idx, ())) == 0
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(asyncio.wait_for(run(), 30))


def test_event_kick_opt_out_leaves_listener_unregistered():
    async def run():
        storage = LocalStorage()
        from rio_tpu.cluster.storage import Member

        await storage.push(Member.from_address("10.6.0.1:90", active=True))
        await storage.push(Member.from_address("10.6.0.2:90", active=True))
        placement = JaxObjectPlacement(mode="greedy", node_axis_size=4)
        daemon = PlacementDaemon(
            storage,
            placement,
            PlacementDaemonConfig(poll_interval=60.0, event_kick=False),
        )
        task = asyncio.create_task(daemon.run())
        try:
            for _ in range(200):
                if daemon.stats.polls >= 1:
                    break
                await asyncio.sleep(0.01)
            assert placement._churn_listeners == []
            placement.cordon("10.6.0.1:90")
            await asyncio.sleep(0.05)
            assert daemon.stats.kicks == 0
        finally:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

    asyncio.run(asyncio.wait_for(run(), 30))
