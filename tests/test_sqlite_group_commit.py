"""Group commit in ``SqliteDb``: one writer thread drains what is queued into
one transaction, commits once, and only then resolves the callers.

The connection under test is a ``sqlite3.Connection`` subclass whose
``commit`` can be held on an ``Event`` or made to fail, so a test decides
what is queued behind the writer instead of racing it.
"""

import asyncio
import dataclasses
import json
import sqlite3
import sys
import threading
from types import SimpleNamespace

import pytest

from rio_tpu import AppData, ServiceObject, message
from rio_tpu.otel import server_gauges
from rio_tpu.state import LocalState, StateProvider, managed_state, save_state
from rio_tpu.state.sqlite import SqliteState
from rio_tpu.utils import sqlite as sqlite_mod
from rio_tpu.utils.sqlite import SqliteDb, SqliteStats

TABLE = ["CREATE TABLE IF NOT EXISTS t (k TEXT PRIMARY KEY, v TEXT NOT NULL);"]
UPSERT = "INSERT INTO t (k, v) VALUES (?, ?) ON CONFLICT(k) DO UPDATE SET v=excluded.v"
ROLLS_BACK = "SELECT 'the whole transaction is rolled back under this statement'"


class Gate:
    """What the test holds over the writer's connection."""

    def __init__(self) -> None:
        self.open = threading.Event()
        self.open.set()
        self.entered = threading.Event()  # a commit has reached the gate
        self.fail_commits = 0

    def hold(self) -> None:
        self.entered.clear()
        self.open.clear()


@pytest.fixture
def gate(monkeypatch):
    gate = Gate()

    class GatedConnection(sqlite3.Connection):
        def commit(self) -> None:
            gate.entered.set()
            assert gate.open.wait(timeout=10), "the test never opened the gate"
            if gate.fail_commits:
                gate.fail_commits -= 1
                raise sqlite3.OperationalError("disk I/O error (injected)")
            super().commit()

        def execute(self, sql, *args):
            if sql == ROLLS_BACK:
                super().execute("ROLLBACK")
                raise sqlite3.OperationalError("database or disk is full (injected)")
            return super().execute(sql, *args)

    def connect(path: str) -> sqlite3.Connection:
        conn = sqlite3.connect(path, factory=GatedConnection)
        conn.execute("PRAGMA journal_mode=WAL")
        conn.execute("PRAGMA busy_timeout=5000")
        return conn

    monkeypatch.setattr(sqlite_mod, "_connect", connect)
    yield gate
    gate.open.set()


def stored(path: str) -> dict[str, str]:
    """The table through a second, read-only connection: committed rows only."""
    conn = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    try:
        return dict(conn.execute("SELECT k, v FROM t").fetchall())
    finally:
        conn.close()


async def plug(db: SqliteDb, gate: Gate) -> asyncio.Task:
    """Hold the writer inside the commit of a lone statement: whatever is
    submitted until the gate opens waits in the queue."""
    gate.hold()
    task = asyncio.create_task(db.execute(UPSERT, "plug", "x"))
    while not gate.entered.is_set():
        await asyncio.sleep(0.001)
    return task


def submit(db: SqliteDb, rows: list[tuple]) -> list[asyncio.Task]:
    return [asyncio.create_task(db.execute(UPSERT, k, v)) for k, v in rows]


def delta(db: SqliteDb, before: SqliteStats) -> tuple[int, int]:
    return db.stats.statements - before.statements, db.stats.commits - before.commits


@pytest.mark.parametrize("n", [1, 2, 32])
def test_no_caller_is_resolved_before_its_commit_returned(tmp_path, gate, n):
    path = str(tmp_path / "a.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        gate.hold()
        tasks = submit(db, [(f"k{i}", str(i)) for i in range(n)])
        while not gate.entered.is_set():
            await asyncio.sleep(0.001)
        await asyncio.sleep(0.05)  # every statement that can run has run
        assert not any(t.done() for t in tasks)
        assert stored(path) == {}
        gate.open.set()
        await asyncio.wait_for(asyncio.gather(*tasks), 10)
        assert stored(path) == {f"k{i}": str(i) for i in range(n)}
        db.close()

    asyncio.run(body())


@pytest.mark.parametrize("n", [2, 19, 32])
def test_statements_queued_behind_the_writer_share_one_commit(tmp_path, gate, n):
    path = str(tmp_path / "b.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        before = dataclasses.replace(db.stats)
        first = await plug(db, gate)
        tasks = submit(db, [(f"k{i}", str(i)) for i in range(n)])
        await asyncio.sleep(0.02)
        gate.open.set()
        await asyncio.wait_for(asyncio.gather(first, *tasks), 10)
        assert delta(db, before) == (n + 1, 2)  # the plug's commit and theirs
        assert db.stats.batch_max == n
        assert len(stored(path)) == n + 1
        db.close()

    asyncio.run(body())


@pytest.mark.parametrize("n", [1, 8])
def test_sequential_awaits_commit_one_by_one(tmp_path, n):
    async def body():
        db = SqliteDb(str(tmp_path / "s.db"))
        await db.migrate(TABLE)
        before = dataclasses.replace(db.stats)
        for i in range(n):
            await db.execute(UPSERT, f"k{i}", str(i))
        assert delta(db, before) == (n, n)
        assert db.stats.batch_max == 1
        db.close()

    asyncio.run(body())


def test_a_burst_without_any_hold_is_stored_whole_in_fewer_commits(tmp_path):
    """No gate: the stress case. 13 loops' worth of callers from threads of
    their own, one key each, under a short switch interval."""
    path = str(tmp_path / "stress.db")
    db = SqliteDb(path)
    asyncio.run(db.migrate(TABLE))
    threads_n, each = 13, 64

    def one(t: int) -> None:
        async def body():
            await asyncio.gather(*(db.execute(UPSERT, f"{t}.{i}", "v") for i in range(each)))

        asyncio.run(body())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=one, args=(t,)) for t in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    db.close()
    assert len(stored(path)) == threads_n * each
    assert db.stats.statements == threads_n * each + 1  # + the migration
    assert db.stats.commits < db.stats.statements


@pytest.mark.parametrize("bad_at", [0, 2, 4])
def test_a_failing_statement_fails_its_caller_alone(tmp_path, gate, bad_at):
    path = str(tmp_path / "c.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        first = await plug(db, gate)
        rows = [(f"k{i}", str(i)) for i in range(5)]
        rows[bad_at] = (f"k{bad_at}", None)  # NOT NULL violated
        tasks = submit(db, rows)
        await asyncio.sleep(0.02)
        gate.open.set()
        await first
        results = await asyncio.wait_for(asyncio.gather(*tasks, return_exceptions=True), 10)
        for i, r in enumerate(results):
            if i == bad_at:
                assert isinstance(r, sqlite3.IntegrityError)
            else:
                assert r == []
        good = {k: v for k, v in rows if v is not None}
        assert stored(path) == {"plug": "x", **good}
        db.close()

    asyncio.run(body())


def test_a_failing_commit_fails_its_whole_batch_and_the_next_succeeds(tmp_path, gate):
    path = str(tmp_path / "d.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        first = await plug(db, gate)
        tasks = submit(db, [("a", "1"), ("b", "2"), ("c", None)])
        await asyncio.sleep(0.02)
        gate.fail_commits = 2  # the plug's, then the batch's
        gate.open.set()
        results = await asyncio.wait_for(
            asyncio.gather(first, *tasks, return_exceptions=True), 10
        )
        assert [type(r) for r in results] == [
            sqlite3.OperationalError,
            sqlite3.OperationalError,
            sqlite3.OperationalError,
            sqlite3.IntegrityError,  # its own error, not the commit's
        ]
        assert stored(path) == {}
        assert await db.execute(UPSERT, "d", "4") == []
        assert stored(path) == {"d": "4"}
        db.close()

    asyncio.run(body())


def test_a_statement_that_rolls_the_transaction_back_fails_those_before_it(tmp_path, gate):
    """Disk full or an I/O error undoes the whole transaction, not the failing
    statement alone: what ran before it in the batch is then in no commit."""
    path = str(tmp_path / "h.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        first = await plug(db, gate)
        before = submit(db, [("a", "1"), ("b", "2")])
        culprit = asyncio.create_task(db.execute(ROLLS_BACK))
        after = submit(db, [("c", "3")])
        await asyncio.sleep(0.02)
        gate.open.set()
        await first
        results = await asyncio.wait_for(
            asyncio.gather(*before, culprit, *after, return_exceptions=True), 10
        )
        assert [type(r) for r in results] == [sqlite3.OperationalError] * 3 + [list]
        assert stored(path) == {"plug": "x", "c": "3"}
        db.close()

    asyncio.run(body())


def test_order_a_task_reads_its_write_and_the_later_of_two_writes_stays(tmp_path, gate):
    path = str(tmp_path / "e.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        await db.execute(UPSERT, "k", "written")
        assert await db.execute("SELECT v FROM t WHERE k=?", "k") == [("written",)]
        first = await plug(db, gate)
        tasks = submit(db, [("k", "earlier"), ("k", "later")])
        read = asyncio.create_task(db.execute("SELECT v FROM t WHERE k=?", "k"))
        await asyncio.sleep(0.02)
        gate.open.set()
        await asyncio.wait_for(asyncio.gather(first, *tasks), 10)
        assert await read == [("later",)]  # queued after both writes
        assert stored(path)["k"] == "later"
        db.close()

    asyncio.run(body())


def test_a_cancelled_waiter_breaks_nothing_and_its_statement_still_ran(tmp_path, gate):
    path = str(tmp_path / "f.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        first = await plug(db, gate)
        gone, stays = submit(db, [("gone", "1"), ("stays", "2")])
        await asyncio.sleep(0.02)
        gone.cancel()
        gate.open.set()
        assert await asyncio.wait_for(stays, 10) == []
        await first
        assert gone.cancelled()
        assert stored(path) == {"plug": "x", "gone": "1", "stays": "2"}
        db.close()

    asyncio.run(body())


def test_a_closed_loop_breaks_nothing_and_the_next_loop_is_served(tmp_path, gate):
    path = str(tmp_path / "g.db")
    db = SqliteDb(path)

    async def first_loop():
        await db.migrate(TABLE)
        await plug(db, gate)
        submit(db, [("left", "behind")])
        await asyncio.sleep(0.02)

    asyncio.run(first_loop())  # cancels both waiters and closes their loop
    gate.open.set()

    async def second_loop():
        assert await db.execute(UPSERT, "second", "loop") == []
        return await db.execute("SELECT k FROM t ORDER BY k")

    assert asyncio.run(second_loop()) == [("left",), ("plug",), ("second",)]
    db.close()


@pytest.mark.parametrize("held", [False, True])
def test_close_with_statements_queued_resolves_them_and_the_object_works_again(
    tmp_path, gate, held
):
    path = str(tmp_path / "close.db")

    async def body():
        db = SqliteDb(path)
        await db.migrate(TABLE)
        tasks = []
        if held:
            tasks.append(await plug(db, gate))
            threading.Timer(0.1, gate.open.set).start()
        tasks += submit(db, [(f"k{i}", str(i)) for i in range(8)])
        await asyncio.sleep(0)  # the tasks have submitted
        writer = db._writer._thread
        db.close()
        assert not writer.is_alive()
        assert db._writer._thread is None
        assert await asyncio.wait_for(asyncio.gather(*tasks), 10) == [[]] * len(tasks)
        assert len(stored(path)) == len(tasks)
        db.close()  # closed already: nothing to do
        assert await db.execute(UPSERT, "again", "1") == []
        assert db._writer._thread is not writer
        assert stored(path)["again"] == "1"
        db.close()

    asyncio.run(body())


def test_a_db_nobody_closed_ends_its_writer_when_it_is_collected(tmp_path):
    async def body():
        db = SqliteDb(str(tmp_path / "gc.db"))
        await db.migrate(TABLE)
        return db._writer._thread

    writer = asyncio.run(body())
    writer.join(timeout=10)
    assert not writer.is_alive()


def test_a_path_that_cannot_be_opened_fails_the_caller_not_the_writer(tmp_path):
    async def body():
        db = SqliteDb(str(tmp_path / "no" / "such" / "dir.db"))
        with pytest.raises(sqlite3.OperationalError):
            await db.execute("SELECT 1")
        with pytest.raises(sqlite3.OperationalError):
            await db.migrate(TABLE)
        db.close()

    asyncio.run(body())


# -- SqliteState on top of it --------------------------------------------------


@message
class Sample:
    total: int = 0


class Meter(ServiceObject):
    sample = managed_state(Sample)


def test_32_concurrent_save_state_are_all_in_the_file(tmp_path):
    path = str(tmp_path / "state.db")

    async def body():
        state = SqliteState(path)
        await state.prepare()
        ctx = AppData().set(state, as_type=StateProvider)
        meters = []
        for i in range(32):
            m = Meter()
            m.id = f"m{i}"
            m.sample = Sample(total=i)
            meters.append(m)
        await asyncio.gather(*(save_state(m, ctx) for m in meters))
        gauges = state.gauges()
        state.close()
        return gauges

    gauges = asyncio.run(body())
    conn = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    rows = conn.execute(
        "SELECT object_id, serialized_state FROM state_provider_object_state"
    ).fetchall()
    conn.close()
    assert {k: json.loads(v) for k, v in rows} == {f"m{i}": {"total": i} for i in range(32)}
    assert gauges["rio.sqlite.statements"] == 33.0  # the migration and 32 saves
    assert 2.0 <= gauges["rio.sqlite.commits"] <= 33.0


@pytest.mark.parametrize("sqlite_state", [True, False])
def test_server_gauges_carry_the_sqlite_counters_only_with_a_sqlite_state(tmp_path, sqlite_state):
    state = SqliteState(str(tmp_path / "g.db")) if sqlite_state else LocalState()
    server = SimpleNamespace(app_data=AppData().set(state, as_type=StateProvider))
    keys = {k for k in server_gauges(server) if k.startswith("rio.sqlite.")}
    expected = {"rio.sqlite.statements", "rio.sqlite.commits", "rio.sqlite.batch_max"}
    assert keys == (expected if sqlite_state else set())
    assert not any(
        k.startswith("rio.sqlite.") for k in server_gauges(SimpleNamespace(app_data=AppData()))
    )


def _open_at_the_barrier(path: str, barrier, results) -> None:
    barrier.wait(30)
    try:
        conn = sqlite_mod._connect(path)
        mode = conn.execute("PRAGMA journal_mode").fetchone()[0]
        conn.close()
        results.put(mode)
    except Exception as e:  # noqa: BLE001 - the parent asserts on what came
        results.put(repr(e))


def test_processes_that_open_a_fresh_file_together_all_get_wal(tmp_path):
    """The workers of one ShardedServer open the node's databases at the
    same moment; the switch to WAL refuses all but one of them at once
    ("database is locked", no busy handler), so the opener asks again."""
    import multiprocessing

    ctx = multiprocessing.get_context("spawn")
    for round_ in range(4):
        path = str(tmp_path / f"fresh{round_}.db")
        barrier, results = ctx.Barrier(6), ctx.Queue()
        procs = [
            ctx.Process(target=_open_at_the_barrier, args=(path, barrier, results))
            for _ in range(6)
        ]
        for p in procs:
            p.start()
        got = [results.get(timeout=60) for _ in procs]  # drained before the joins
        for p in procs:
            p.join(30)
            assert not p.is_alive()
        assert got == ["wal"] * 6
