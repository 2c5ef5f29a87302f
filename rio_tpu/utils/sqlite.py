"""Shared async SQLite helper for the sql-backed providers.

The reference uses sqlx pools (``rio-rs/src/cluster/storage/sqlite.rs``,
``object_placement/sqlite.rs``, ``state/sqlite.rs``); Python's stdlib
``sqlite3`` is synchronous, so each :class:`SqliteDb` has ONE writer thread
that owns its one connection. ``execute`` queues a statement and awaits its
future; the writer takes everything that is queued at the moment it is free,
runs the statements in order in one transaction, commits ONCE and only then
resolves their callers (group commit). A lone statement is a batch of one: a
statement and its commit, as if there were no queue. Under concurrent callers
the commits per acknowledged statement fall, never a statement's durability
at its acknowledgement: nothing is resolved before the commit that covers it
has returned, and nothing waits on a timer. ``stats`` says how often the
grouping engages (statements per commit).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import sqlite3
import threading
import time
import weakref
from collections import deque
from typing import Any

# The most statements one transaction takes: bounds how long the first caller
# of a burst waits for the last one's statement, and the results held at once.
_BATCH_MAX = 1024


@dataclasses.dataclass
class SqliteStats:
    """``statements / commits`` is the grouping's hit share (1 = bypassed)."""

    statements: int = 0
    commits: int = 0
    batch_max: int = 0


def _connect(path: str) -> sqlite3.Connection:
    conn = sqlite3.connect(path)
    conn.execute("PRAGMA busy_timeout=5000")
    # The switch to WAL wants the file to itself, and SQLite refuses two
    # openers that ask at once without calling the busy handler (it would
    # deadlock): the workers of one ShardedServer open a fresh file
    # together. Ask again; on a file already in WAL it is a read.
    deadline = time.monotonic() + 5.0
    while True:
        try:
            conn.execute("PRAGMA journal_mode=WAL")
            return conn
        except sqlite3.OperationalError as e:
            if "locked" not in str(e) or time.monotonic() > deadline:
                conn.close()
                raise
            time.sleep(0.005)


class _Writer:
    """The thread, its connection and its queue.

    Apart from :class:`SqliteDb` so that the thread holds no reference to it:
    a ``SqliteDb`` nobody closed is still collected, and its finalizer ends
    the thread and with it the connection.
    """

    def __init__(self, path: str) -> None:
        self.path = path
        self.stats = SqliteStats()
        # (sql, params, future) in arrival order; params None marks a script.
        self._queue: deque[tuple[str, tuple | None, asyncio.Future]] = deque()
        self._cond = threading.Condition()
        self._thread: threading.Thread | None = None
        self._closing = False

    def submit(self, sql: str, params: tuple | None) -> asyncio.Future:
        fut = asyncio.get_running_loop().create_future()
        with self._cond:
            self._queue.append((sql, params, fut))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._write, name=f"sqlite-writer:{self.path}", daemon=True
                )
                self._thread.start()
            self._cond.notify()
        return fut

    def stop(self) -> threading.Thread | None:
        """Tell the thread to drain, close and end; the thread to join, if any."""
        with self._cond:
            if self._thread is not None:
                self._closing = True
                self._cond.notify()
            return self._thread

    def _take(self) -> list[tuple[str, tuple | None, asyncio.Future]] | None:
        """Everything queued now (at most ``_BATCH_MAX``); None once stopped."""
        with self._cond:
            while not self._queue:
                if self._closing:
                    # Under the lock: a submit after this starts a new thread.
                    self._thread = None
                    self._closing = False
                    return None
                self._cond.wait()
            take = min(len(self._queue), _BATCH_MAX)
            return [self._queue.popleft() for _ in range(take)]

    def _write(self) -> None:
        conn: sqlite3.Connection | None = None
        try:
            while (batch := self._take()) is not None:
                try:
                    if conn is None:
                        conn = _connect(self.path)
                except sqlite3.Error as e:
                    _resolve([(fut, None, e) for _, _, fut in batch])
                    continue
                _resolve(self._run(conn, batch))
        finally:
            if conn is not None:
                conn.close()

    def _run(self, conn: sqlite3.Connection, batch: list) -> list[tuple]:
        """One transaction over ``batch``; its outcomes, commit included."""
        outcomes: list[tuple[asyncio.Future, list[tuple] | None, Exception | None]] = []
        for sql, params, fut in batch:
            in_transaction = conn.in_transaction
            try:
                if params is None:
                    # executescript commits an open transaction itself: give
                    # that commit to the callers it covers first.
                    if in_transaction:
                        outcomes = self._commit(conn, outcomes)
                        in_transaction = False
                    conn.executescript(sql)
                    rows: list[tuple] = []
                else:
                    rows = conn.execute(sql, params).fetchall()
            except Exception as e:  # the writer outlives any statement: the caller gets it
                if in_transaction and not conn.in_transaction:
                    # SQLite undoes a failing statement alone, but some errors
                    # (disk full, I/O) roll the whole transaction back: then
                    # nothing before this statement will be committed either.
                    outcomes = _failed(outcomes, e)
                outcomes.append((fut, None, e))
            else:
                outcomes.append((fut, rows, None))
        self.stats.statements += len(batch)
        self.stats.batch_max = max(self.stats.batch_max, len(batch))
        return self._commit(conn, outcomes)

    def _commit(self, conn: sqlite3.Connection, outcomes: list[tuple]) -> list[tuple]:
        self.stats.commits += 1
        try:
            conn.commit()
        except sqlite3.Error as e:
            with contextlib.suppress(sqlite3.Error):
                conn.rollback()
            return _failed(outcomes, e)
        return outcomes


class SqliteDb:
    def __init__(self, path: str) -> None:
        self.path = path
        self._writer = _Writer(path)
        self.stats = self._writer.stats
        weakref.finalize(self, self._writer.stop)

    async def execute(self, sql: str, *params: Any) -> list[tuple]:
        return await self._writer.submit(sql, params)

    async def migrate(self, queries: list[str]) -> None:
        """Run migration statements (reference ``sql_migration.rs``)."""
        for q in queries:
            await self._writer.submit(q, None)

    def close(self) -> None:
        """Drain what is queued, close the connection, end the writer thread.

        Every waiter is resolved or failed as usual; the next ``execute``
        starts a new thread on a new connection.
        """
        thread = self._writer.stop()
        if thread is not None:
            thread.join()


def _failed(outcomes: list[tuple], error: Exception) -> list[tuple]:
    """``outcomes`` once their transaction is lost: a statement's own error stays."""
    return [(fut, None, exc or error) for fut, _, exc in outcomes]


def _resolve(outcomes: list[tuple]) -> None:
    """Hand a committed batch's outcomes back: one call per loop with callers in it."""
    by_loop: dict[asyncio.AbstractEventLoop, list[tuple]] = {}
    for outcome in outcomes:
        by_loop.setdefault(outcome[0].get_loop(), []).append(outcome)
    for loop, mine in by_loop.items():
        # A closed loop has no waiter left; its statements ran all the same.
        with contextlib.suppress(RuntimeError):
            loop.call_soon_threadsafe(_deliver, mine)


def _deliver(outcomes: list[tuple]) -> None:
    for fut, rows, exc in outcomes:
        if fut.done():  # cancelled while it waited
            continue
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(rows)
