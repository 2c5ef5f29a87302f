"""Durability bridge: solver-speed directory with a write-behind backing store.

:class:`~rio_tpu.object_placement.jax_placement.JaxObjectPlacement` keeps
the directory in a host mirror for O(1) lookups and batched device solves
— a restart loses it and relies on lazy re-allocation (the reference's
recovery path, ``rio-rs/src/service.rs:227-298``). A rio-rs user migrating
from ``SqliteObjectPlacement`` gives up the durability they had.

:class:`PersistentJaxObjectPlacement` closes that gap without giving the
speed back: every mirror mutation (allocation, update, rebalance apply,
clean_server, remove) marks the key dirty, and a background flusher
coalesces the dirty set into batched writes against ANY reference-style
``ObjectPlacement`` backing store (SQLite / Postgres / Redis — whatever
the deployment already runs). ``prepare()`` warm-restores the whole
directory from the backing store via the trait's ``items()`` hook.

Consistency model — write-BEHIND, deliberately:

* the solver path never waits on the database (the whole point of the
  provider is removing the per-request SQL round trip);
* a crash loses at most ``flush_interval`` worth of placements, each of
  which lazy re-allocation re-seats on first touch — the same recovery
  the non-persistent provider relies on for EVERYTHING;
* flush failures keep the dirty set (newer marks win the merge) and retry
  on the next cycle — the backing store being briefly down degrades
  durability freshness, never availability.
"""

from __future__ import annotations

import asyncio
import logging

from ..registry import ObjectId
from . import ObjectPlacement, ObjectPlacementItem, sanitize_standby_row
from .jax_placement import JaxObjectPlacement

log = logging.getLogger("rio_tpu.object_placement.persistent")

__all__ = ["PersistentJaxObjectPlacement"]


class PersistentJaxObjectPlacement(JaxObjectPlacement):
    """JaxObjectPlacement + write-behind durability on a backing store."""

    def __init__(
        self,
        backing: ObjectPlacement,
        *,
        flush_interval: float = 0.05,
        **jax_kwargs,
    ) -> None:
        super().__init__(**jax_kwargs)
        self._backing = backing
        self._flush_interval = flush_interval
        self._dirty: dict[str, str | None] = {}  # key -> address | None=delete
        self._dirty_standbys: dict[str, list[str]] = {}  # key -> standby set
        self._flusher: asyncio.Task | None = None
        self._flush_wake: asyncio.Event | None = None  # created on the loop
        self._flush_lock = asyncio.Lock()  # serializes manual + background
        self._restoring = False

    # ------------------------------------------------------------- restore
    async def prepare(self) -> None:
        """Warm-restore the mirror from the backing store (once, at boot)."""
        await self._backing.prepare()
        items = await self._backing.items()
        async with self._lock:
            self._restoring = True
            known = set(self._nodes)
            try:
                for item in items:
                    if item.server_address is not None:
                        self._set_placement(
                            str(item.object_id),
                            self._node_index(item.server_address),
                        )
            finally:
                self._restoring = False
            # Nodes the restore itself had to invent are HEARSAY from the
            # stored directory — the node may have died while we were down.
            # Start them dead (sync_members/register_node revives the live
            # ones) so the solver never seats NEW objects on a ghost; their
            # restored placements stand until lookup/gossip re-seats them.
            for address in set(self._nodes) - known:
                self._nodes[address].alive = False
            # The restored population must count as load, or the next
            # allocation treats the cluster as empty and piles onto the
            # fullest node.
            self._recount_loads()
            if items:
                self._epoch += 1
        log.info("restored %d placements from %s",
                 len(items), type(self._backing).__name__)

    # ------------------------------------------------------- dirty tracking
    # Every mirror mutation in the base class flows through these three
    # methods (allocation apply in bulk through ``_seat_new``; rebalance
    # mover loop, update, remove, clean_server per key), so overriding
    # them catches the full write set.
    def _set_placement(self, key: str, idx: int) -> bool:
        changed = super()._set_placement(key, idx)
        if changed and not self._restoring:
            self._mark(key, self._node_order[idx])
        return changed

    def _drop_placement(self, key: str) -> int | None:
        idx = super()._drop_placement(key)
        if idx is not None and not self._restoring:
            self._mark(key, None)
        return idx

    def _seat_new(self, keys, idx):
        counts = super()._seat_new(keys, idx)
        if keys and not self._restoring:
            self._dirty.update(
                zip(keys, map(self._node_order.__getitem__, idx.tolist()))
            )
            self._wake_flusher()
        return counts

    def _set_standby_row(self, key: str, addresses: list[str], epoch: int) -> None:
        super()._set_standby_row(key, addresses, epoch)
        if not self._restoring:
            self._dirty_standbys[key] = list(addresses)
            self._wake_flusher()

    def _drop_standby_row(self, key: str) -> None:
        super()._drop_standby_row(key)
        if not self._restoring:
            self._dirty_standbys[key] = []
            self._wake_flusher()

    def _mark(self, key: str, address: str | None) -> None:
        self._dirty[key] = address
        self._wake_flusher()

    def _wake_flusher(self) -> None:
        if self._flush_wake is None:
            self._flush_wake = asyncio.Event()
        self._flush_wake.set()
        if self._flusher is None or self._flusher.done():
            self._flusher = asyncio.get_running_loop().create_task(
                self._flush_loop()
            )

    # --------------------------------------------------------------- flush
    async def _flush_loop(self) -> None:
        assert self._flush_wake is not None
        while True:
            await self._flush_wake.wait()
            self._flush_wake.clear()
            # Coalesce a burst (one rebalance marks ~the displaced share)
            # into one batched write instead of thousands.
            await asyncio.sleep(self._flush_interval)
            try:
                await self.flush()
            except Exception:
                log.exception("placement write-behind flush failed; retrying")
                await asyncio.sleep(self._flush_interval)
                self._flush_wake.set()

    async def flush(self) -> int:
        """Write the current dirty set to the backing store (also callable
        directly, e.g. before a planned shutdown). Returns rows written.

        Serialized against the background flusher: a manual flush must not
        return while an in-flight background write still holds part of the
        dirty set — "flush then stop" would otherwise race its own flusher.
        """
        async with self._flush_lock:
            return await self._flush_locked()

    async def _flush_locked(self) -> int:
        flushed = await self._flush_standbys_locked()
        if not self._dirty:
            return flushed
        dirty, self._dirty = self._dirty, {}
        try:
            # ONE batched write for updates AND deletes: every backend's
            # update_batch treats server_address=None as unassign (Redis
            # pipelines SREM+DEL, SQL upserts NULL which lookup/items treat
            # as absent). Per-key awaited removes would turn a big
            # clean_server (500k keys at the 10M tier) into minutes of
            # round trips and blow the crash-loss window.
            await self._backing.update_batch(
                [
                    ObjectPlacementItem(ObjectId(*k.split(".", 1)), addr)
                    for k, addr in dirty.items()
                ]
            )
        except BaseException:
            # Keep failed rows dirty; marks made DURING the failed flush
            # are newer and win the merge. BaseException on purpose: a
            # flusher CANCELLED mid-write (aclose during a flush) must
            # also put its unwritten marks back for the final flush.
            for k, addr in dirty.items():
                self._dirty.setdefault(k, addr)
            raise
        return flushed + len(dirty)

    async def _flush_standbys_locked(self) -> int:
        if not self._dirty_standbys:
            return 0
        dirty, self._dirty_standbys = self._dirty_standbys, {}
        done = 0
        try:
            # Per-key writes (the trait has no standby batch hook): replica
            # sets change at placement/repair cadence, not per request, so
            # the write volume is nothing like the primary-row stream. The
            # backing preserves its own epoch — only promote_standby (write-
            # THROUGH below) ever moves it.
            for k, addrs in list(dirty.items()):
                await self._backing.set_standbys(
                    ObjectId(*k.split(".", 1)), addrs
                )
                dirty.pop(k)
                done += 1
        except BaseException:
            for k, addrs in dirty.items():
                self._dirty_standbys.setdefault(k, addrs)
            raise
        return done

    # ------------------------------------------------------- replica rows
    # Standby SETS ride the write-behind like primary rows; the EPOCH is
    # different — it is the failover fence, so it must be durable the
    # instant it moves. promote_standby is therefore write-THROUGH: the
    # backing store's CAS is the arbiter, the mirror follows its verdict.

    async def standbys(self, object_id) -> tuple[list[str], int]:
        key = str(object_id)
        row = self._standby_rows.get(key)
        if row is not None:
            held, epoch = row
            return sanitize_standby_row(held, epoch)
        # Mirror miss (cold restart): read through. Not cached — a row is
        # only mirrored once this node writes it, keeping restore lazy.
        return await self._backing.standbys(object_id)

    async def set_standbys(self, object_id, addresses: list[str]) -> int:
        # Seed the mirror with the BACKING's epoch on first touch after a
        # restart, or the returned fence would restart at 0 while the
        # durable row is ahead of it.
        key = str(object_id)
        if key not in self._standby_rows:
            _, epoch = await self._backing.standbys(object_id)
            async with self._lock:
                if key not in self._standby_rows:
                    self._set_standby_row(key, list(addresses), epoch)
                    return epoch
        return await super().set_standbys(object_id, addresses)

    async def promote_standby(
        self, object_id, address: str, expected_epoch: int
    ) -> int | None:
        # The durable CAS must see this node's standby writes first.
        await self.flush()
        new_epoch = await self._backing.promote_standby(
            object_id, address, expected_epoch
        )
        if new_epoch is None:
            return None
        key = str(object_id)
        # Cold-restart mirror miss: rebuilding the row from ([], 0) would
        # flush an EMPTY set over the surviving standbys' durable row with
        # k>=2, silently dropping seats until anti-entropy re-places them.
        # The post-CAS backing row is authoritative (it already excludes
        # the promoted address).
        survivors: list[str] | None = None
        if key not in self._standby_rows:
            survivors, _ = await self._backing.standbys(object_id)
        async with self._lock:
            row = self._standby_rows.get(key)
            if row is not None:
                survivors = [a for a in row[0] if a != address]
            self._set_standby_row(key, survivors or [], new_epoch)
            self._set_placement(key, self._node_index(address))
            self._epoch += 1
        return new_epoch

    async def aclose(self) -> None:
        """Final flush + stop the flusher (planned shutdown)."""
        if self._flusher is not None:
            self._flusher.cancel()
            try:
                await self._flusher
            except asyncio.CancelledError:
                pass
            self._flusher = None
        await self.flush()
