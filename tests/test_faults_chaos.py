"""Chaos soak: kill the rendezvous under live load, survive, reconverge.

The capstone scenario of the fault-injection PR: a real multi-server
cluster (shared in-memory rendezvous wrapped in the fault-injection
layer) serving live counter traffic while the membership AND placement
storage die completely for a scripted window. The contract under test:

* **zero lost acked writes** — every increment the client saw acked is in
  the final counter value (and nothing is double-applied);
* **seated traffic flows** — actors already resident keep serving from
  the local registry while the directory is down;
* **new placements shed retryably** — unseated keys get SERVER_BUSY (the
  client's backoff + re-route path), never a hang or a poisoned error;
* **bounded reconvergence** — after heal, previously-shed keys place and
  serve within a small deadline;
* **a causal journal story** — the servers' journals carry STORAGE
  degraded/recovered edges for the outage.
"""

import asyncio
import os

import pytest

from rio_tpu import AppData, Registry, ServiceObject, handler, message
from rio_tpu.cluster.storage import LocalStorage
from rio_tpu.errors import (
    ClientError,
    Disconnect,
    RetryExhausted,
    ServerBusy,
    ServerNotAvailable,
)
from rio_tpu.faults import (
    FaultSchedule,
    FaultyMembershipStorage,
    FaultyObjectPlacement,
    StorageHealth,
)
from rio_tpu.journal import STORAGE
from rio_tpu.object_placement import LocalObjectPlacement
from rio_tpu.utils import ExponentialBackoff

from .server_utils import Cluster, run_integration_test

RETRYABLE = (RetryExhausted, ServerBusy, ServerNotAvailable, Disconnect, OSError)


@message
class Add:
    n: int = 1


@message
class Get:
    pass


@message
class Total:
    value: int = 0


class Counter(ServiceObject):
    def __init__(self):
        self.value = 0

    @handler
    async def add(self, msg: Add, ctx: AppData) -> Total:
        self.value += msg.n
        return Total(value=self.value)

    @handler
    async def get(self, msg: Get, ctx: AppData) -> Total:
        return Total(value=self.value)


def build_registry() -> Registry:
    r = Registry()
    r.add_type(Counter)
    return r


def _fast_backoff() -> ExponentialBackoff:
    return ExponentialBackoff(initial=0.01, cap=0.05, max_retries=4)


async def _soak(outage_secs: float, seated: int, writers_per_key: int) -> None:
    schedule = FaultSchedule(seed=1234)
    wrapper_health = StorageHealth()
    members = FaultyMembershipStorage(LocalStorage(), schedule, wrapper_health)
    placement = FaultyObjectPlacement(
        LocalObjectPlacement(), schedule, wrapper_health
    )

    async def body(cluster: Cluster):
        client = cluster.client(backoff=_fast_backoff())
        acked: dict[str, int] = {f"c{i}": 0 for i in range(seated)}

        async def ack_add(key: str) -> bool:
            try:
                await client.send(Counter, key, Add(n=1), returns=Total)
            except RETRYABLE:
                return False
            acked[key] += 1
            return True

        # Phase 1 — healthy: seat every counter and bank some writes.
        for key in acked:
            assert await ack_add(key), "healthy write failed"

        # Phase 2 — the rendezvous dies, live load continues.
        schedule.fail_all("membership.*")
        schedule.fail_all("placement.*")
        sheds = 0
        stop = asyncio.get_event_loop().time() + outage_secs

        async def writer(key: str):
            while asyncio.get_event_loop().time() < stop:
                await ack_add(key)
                await asyncio.sleep(0.002)

        async def cold_traffic():
            # New keys during the outage must shed retryably, not hang:
            # each attempt is bounded by the client's (fast) retry budget.
            nonlocal sheds
            i = 0
            while asyncio.get_event_loop().time() < stop:
                i += 1
                try:
                    await asyncio.wait_for(
                        client.send(Counter, f"cold-{i}", Add(n=1), returns=Total),
                        timeout=5.0,
                    )
                except RETRYABLE:
                    sheds += 1
                except ClientError:
                    sheds += 1
                await asyncio.sleep(0.01)

        await asyncio.gather(
            *(writer(k) for k in acked for _ in range(writers_per_key)),
            cold_traffic(),
        )

        outage_served = sum(acked.values())
        assert outage_served > seated, "no seated traffic flowed during the outage"
        assert sheds > 0, "no cold key was shed during the outage"

        # Phase 3 — heal; bounded reconvergence for a previously-shed key.
        schedule.heal()
        deadline = asyncio.get_event_loop().time() + 10.0
        placed = False
        while asyncio.get_event_loop().time() < deadline:
            try:
                await client.send(Counter, "cold-after", Add(n=1), returns=Total)
                placed = True
                break
            except RETRYABLE:
                await asyncio.sleep(0.05)
        assert placed, "cluster did not reconverge within the deadline"

        # Zero lost (and zero duplicated) acked writes.
        for key, want in acked.items():
            got = await client.send(Counter, key, Get(), returns=Total)
            assert got.value == want, f"{key}: acked {want} writes, found {got.value}"

        # Observability story: some server served seated traffic degraded
        # and/or shed cold keys, and journaled the outage edges.
        degraded_serves = sum(s.storage_health.degraded_serves for s in cluster.servers)
        shed_count = sum(s.storage_health.sheds for s in cluster.servers)
        assert degraded_serves > 0, "no degraded-mode serve was recorded"
        assert shed_count > 0, "no retryable shed was recorded"
        def storage_modes(server) -> list:
            return [
                ev.attrs.get("mode")
                for ev in server.journal.events()
                if ev.kind == STORAGE
            ]

        # A server journals the recovered edge at ITS next good storage call
        # (its heartbeat push, 0.2 s apart, or a request it routes), not at
        # the heal: the requests above need not have touched every server.
        # Bounded like the reconvergence above; what is asserted stays.
        deadline = asyncio.get_event_loop().time() + 10.0
        while asyncio.get_event_loop().time() < deadline and any(
            "degraded" in m and "recovered" not in m
            for m in map(storage_modes, cluster.servers)
        ):
            await asyncio.sleep(0.05)
        for server in cluster.servers:
            modes = storage_modes(server)
            if "degraded" in modes:
                assert "recovered" in modes, (
                    f"{server.local_address}: STORAGE degraded without recovery"
                )
        assert any(
            ev.kind == STORAGE
            for s in cluster.servers
            for ev in s.journal.events()
        ), "no STORAGE journal events anywhere"
        client.close()

    await run_integration_test(
        body,
        registry_builder=build_registry,
        num_servers=2,
        members=members,
        placement=placement,
        timeout=90.0,
    )


def test_rendezvous_outage_soak_fast():
    """Tier-1 chaos soak: a short scripted outage under live load."""
    asyncio.run(_soak(outage_secs=1.0, seated=4, writers_per_key=2))


@pytest.mark.slow
def test_rendezvous_outage_soak_long():
    """Slow-lane soak: longer outage, more keys, more writers.

    ``RIO_TPU_CHAOS_SECS`` stretches the outage window (nightly chaos
    matrix runs it at tens of seconds)."""
    secs = float(os.environ.get("RIO_TPU_CHAOS_SECS", "5"))
    asyncio.run(_soak(outage_secs=secs, seated=8, writers_per_key=4))


def test_outage_with_hang_sheds_via_route_timeout():
    """A HUNG (not erroring) rendezvous: without ``route_timeout`` the
    request path would await the directory forever; with it, unseated
    requests shed within the bound and seated ones keep serving."""
    from rio_tpu.faults import StorageResilienceConfig

    schedule = FaultSchedule(seed=5)
    members = FaultyMembershipStorage(LocalStorage(), schedule)
    placement = FaultyObjectPlacement(LocalObjectPlacement(), schedule)

    async def body(cluster: Cluster):
        client = cluster.client(backoff=_fast_backoff())
        await client.send(Counter, "seated", Add(n=1), returns=Total)

        schedule.fail_all("placement.*", hang=True)
        # Seated: served from the registry without touching the directory
        # once the route timeout fires.
        t = await asyncio.wait_for(
            client.send(Counter, "seated", Add(n=1), returns=Total), timeout=5.0
        )
        assert t.value == 2
        # Unseated: the hung lookup times out server-side and sheds; the
        # client's bounded retries surface it as retryable, never a hang.
        with pytest.raises(RETRYABLE):
            await asyncio.wait_for(
                client.send(Counter, "cold", Add(n=1), returns=Total), timeout=5.0
            )
        schedule.heal()
        t = await client.send(Counter, "cold", Add(n=1), returns=Total)
        assert t.value == 1
        client.close()

    def app_data() -> AppData:
        data = AppData()
        data.set(StorageResilienceConfig(route_timeout=0.2))
        return data

    asyncio.run(
        run_integration_test(
            body,
            registry_builder=build_registry,
            num_servers=2,
            members=members,
            placement=placement,
            app_data_builder=app_data,
            timeout=60.0,
        )
    )
