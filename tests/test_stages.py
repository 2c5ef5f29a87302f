"""The stage log (``rio_tpu.tracing.stage``), the stages the directory logs,
the load monitors' loop counters, and the saves' RED row.

What must hold: stages are coarse (per batch call, per solve, per full
collection — nothing per request or per key), the log is bounded and always
on, and none of it starts a task or a thread (the one timer a loop is the
loop's own clock: ``tests/test_loop_holds.py``).
"""

import asyncio
import contextlib
import gc
import threading
import time
import types
import weakref

import pytest

from rio_tpu import AppData, LoadMonitor, ObjectId, Registry, ServiceObject, handler, message
from rio_tpu import tracing
from rio_tpu.metrics import MetricsRegistry
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement
from rio_tpu.otel import server_gauges
from rio_tpu.state import SAVE_METRIC_KEY, LocalState, StateProvider, managed_state, save_state

from .server_utils import Cluster, run_integration_test

# name, t0_ns, t1_ns, parent, call_id, thread_id
NAME, T0, T1, PARENT, CALL, THREAD = range(6)

CHUNK_STAGES = (
    "place.lock_wait", "place.filter", "place.snapshot", "place.solve",
    "place.solve.build", "place.solve.wait", "place.solve.route",
    "place.resume", "place.apply",
)


@pytest.fixture(autouse=True)
def fresh_log():
    tracing.clear_stages()
    yield
    tracing.clear_stages()


def _placement(nodes: int = 8) -> JaxObjectPlacement:
    p = JaxObjectPlacement(mode="greedy")
    for i in range(nodes):
        p.register_node(f"10.0.0.{i}:7000")
    return p


def _ids(lo: int, hi: int) -> list:
    return [ObjectId("T", str(i)) for i in range(lo, hi)]


def _union_ns(intervals) -> int:
    total, end = 0, -1
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


# ---------------------------------------------------------------------------
# The primitive
# ---------------------------------------------------------------------------


def test_stages_nest_and_carry_parent_and_call_id():
    with tracing.stage("outer") as outer:
        with tracing.stage("inner"):
            pass
        with tracing.stage("inner"):
            pass
    with tracing.stage("other"):
        pass
    log = tracing.stage_log()
    assert [r[NAME] for r in log] == ["inner", "inner", "outer", "other"]
    inner1, inner2, out, other = log
    assert inner1[PARENT] == inner2[PARENT] == "outer" and out[PARENT] is None
    assert inner1[CALL] == inner2[CALL] == out[CALL] != 0
    assert other[CALL] not in (0, out[CALL]) and other[PARENT] is None
    assert out[T0] <= inner1[T0] <= inner1[T1] <= inner2[T0] <= inner2[T1] <= out[T1]
    assert (out[T0], out[T1]) == (outer.t0, outer.t1)
    assert outer.ms == pytest.approx((out[T1] - out[T0]) / 1e6)
    assert all(r[THREAD] == threading.get_ident() for r in log)
    assert tracing.stage_totals()["inner"][0] == 2


async def test_a_stage_entered_in_a_worker_thread_logs_that_thread():
    def work() -> int:
        with tracing.stage("child") as st:
            pass
        return st.t1

    with tracing.stage("root"):
        t1 = await asyncio.to_thread(work)
        tracing.stage_since("waited", t1)
    child, waited, root = tracing.stage_log()
    assert (child[NAME], waited[NAME], root[NAME]) == ("child", "waited", "root")
    assert child[THREAD] != root[THREAD] == waited[THREAD] == threading.get_ident()
    assert child[PARENT] == waited[PARENT] == "root"
    assert child[CALL] == waited[CALL] == root[CALL]
    assert waited[T0] == child[T1] and waited[T1] >= waited[T0]


def test_the_stage_log_is_bounded():
    for _ in range(tracing.STAGE_LOG_SIZE + 100):
        with tracing.stage("tick"):
            pass
    assert len(tracing.stage_log()) == tracing.STAGE_LOG_SIZE
    assert tracing.stage_totals()["tick"][0] == tracing.STAGE_LOG_SIZE + 100


def test_a_stage_that_raises_is_logged_and_unwinds_the_context():
    with pytest.raises(KeyError):
        with tracing.stage("outer"):
            with tracing.stage("inner"):
                raise KeyError("x")
    assert [r[NAME] for r in tracing.stage_log()] == ["inner", "outer"]
    with tracing.stage("after"):
        pass
    assert tracing.stage_log()[-1][PARENT] is None


# ---------------------------------------------------------------------------
# The directory's stages
# ---------------------------------------------------------------------------


async def test_assign_batch_logs_every_stage_once_per_chunk_and_tiles_the_call(monkeypatch):
    p = _placement()
    await p.assign_batch(_ids(0, 64))  # the chunk shape's compile is not the test's
    monkeypatch.setattr(JaxObjectPlacement, "_MAX_PLACE_CHUNK", 1024)
    tracing.clear_stages()
    await p.assign_batch(_ids(1000, 1000 + 2048))
    log = tracing.stage_log()
    names = [r[NAME] for r in log]
    for name in CHUNK_STAGES:
        # nobody else wrote between the two holds: the answer is built inside
        # the last chunk's, and no further lock_wait or filter pass runs
        assert names.count(name) == 2, (name, names)
    for name in ("place.assign", "place.keys", "place.resolve"):
        assert names.count(name) == 1, (name, names)
    assert set(names) == set(CHUNK_STAGES) | {"place.assign", "place.keys", "place.resolve"}
    root = log[-1]
    assert root[NAME] == "place.assign" and root[PARENT] is None
    assert {r[CALL] for r in log} == {root[CALL]}
    loop_thread = threading.get_ident()
    for r in log:
        in_thread = r[NAME].startswith("place.solve")
        assert (r[THREAD] != loop_thread) == in_thread, r
        want = "place.solve" if r[NAME].startswith("place.solve.") else "place.assign"
        assert r[PARENT] == (None if r is root else want), r
    children = [(r[T0], r[T1]) for r in log if r[PARENT] == "place.assign"]
    assert all(root[T0] <= a <= b <= root[T1] for a, b in children)
    assert _union_ns(children) >= 0.9 * (root[T1] - root[T0])
    solves = [r for r in log if r[NAME] == "place.solve"]
    for s in solves:
        kids = [(r[T0], r[T1]) for r in log
                if r[PARENT] == "place.solve" and s[T0] <= r[T0] and r[T1] <= s[T1]]
        assert len(kids) == 3 and _union_ns(kids) >= 0.9 * (s[T1] - s[T0])
    # place.resume starts at the instant its solve ended in the thread
    resumes = [r for r in log if r[NAME] == "place.resume"]
    assert [r[T0] for r in resumes] == [s[T1] for s in solves]


async def test_lookup_batch_is_one_stage():
    p = _placement()
    await p.assign_batch(_ids(0, 32))
    tracing.clear_stages()
    assert None not in await p.lookup_batch(_ids(0, 32))
    (rec,) = tracing.stage_log()
    assert rec[NAME] == "place.lookup" and rec[PARENT] is None and rec[CALL] != 0


@pytest.mark.parametrize("delta", [False, None])
async def test_a_solve_is_one_stage_whose_children_fill_solvestats(delta):
    p = _placement()
    await p.assign_batch(_ids(0, 512))
    await p.rebalance(delta=False)
    tracing.clear_stages()
    await p.rebalance(delta=delta)
    log = tracing.stage_log()
    by_name = {r[NAME]: r for r in log}
    root = by_name["solve.full"]
    assert root[PARENT] is None and {r[CALL] for r in log} == {root[CALL]}
    for name in ("solve.snapshot", "solve.device", "solve.apply"):
        assert by_name[name][PARENT] == "solve.full"
        assert root[T0] <= by_name[name][T0] <= by_name[name][T1] <= root[T1]
    assert by_name["solve.device"][THREAD] != root[THREAD]
    assert by_name["solve.snapshot"][THREAD] == by_name["solve.apply"][THREAD] == root[THREAD]
    # one pair of clock reads: the record IS the stage the stats were filled from
    dev, app = by_name["solve.device"], by_name["solve.apply"]
    assert p.stats.solve_ms == (dev[T1] - dev[T0]) / 1e6
    assert p.stats.apply_ms == (app[T1] - app[T0]) / 1e6
    assert p.stats.compile_ms + p.stats.exec_ms == pytest.approx(p.stats.solve_ms, abs=2e-3)


async def test_a_solve_with_an_object_costs_hook_logs_its_features():
    calls = []

    def costs(keys):
        calls.append(len(keys))
        return [1.0 + (i % 3) for i in range(len(keys))]

    p = JaxObjectPlacement(mode="greedy", object_costs=costs)
    for i in range(4):
        p.register_node(f"10.0.0.{i}:7000")
    await p.assign_batch(_ids(0, 64))
    tracing.clear_stages()
    await p.rebalance(delta=False)
    feats = [r for r in tracing.stage_log() if r[NAME] == "solve.features"]
    assert calls and len(feats) == 1 and feats[0][PARENT] == "solve.device"


class Echo(ServiceObject):
    def __init__(self):
        self.n = 0

    @handler
    async def ping(self, msg: "Ping", ctx: AppData) -> "Pong":
        self.n += 1
        return Pong(n=self.n)


@message
class Ping:
    pass


@message
class Pong:
    n: int = 0


def test_two_thousand_requests_to_an_actor_log_no_stage():
    placement = _placement(0)

    async def body(cluster: Cluster):
        client = cluster.client()
        await client.send(Echo, "e0", Ping(), returns=Pong)  # seat, activate
        tracing.clear_stages()
        for i in range(2000):
            out = await client.send(Echo, f"e{i % 4}", Ping(), returns=Pong)
        assert out.n >= 500
        client.close()
        # A full collection may fall into the run; nothing else may be logged.
        assert [r[NAME] for r in tracing.stage_log() if r[NAME] != "gc.gen2"] == []
        totals = tracing.stage_totals()
        assert {k for k in totals if not k.startswith("gc.")} == set()

    asyncio.run(run_integration_test(
        body, registry_builder=lambda: Registry().add_type(Echo), num_servers=2,
        placement=placement,
    ))


async def test_directory_calls_and_collections_leave_no_task_thread_or_timer():
    p = _placement()
    await p.assign_batch(_ids(0, 16))  # the to_thread pool's workers exist from here
    await p.rebalance(delta=False)
    loop = asyncio.get_running_loop()
    tasks0, threads0 = len(asyncio.all_tasks()), set(threading.enumerate())
    timers0 = len(loop._scheduled)
    tracing.watch_gc()
    try:
        await p.assign_batch(_ids(100, 400))
        await p.lookup_batch(_ids(0, 400))
        await p.rebalance(delta=False)
        gc.collect()
    finally:
        tracing.unwatch_gc()
    assert {"place.assign", "place.lookup", "solve.full", "gc.gen2"} <= {
        r[NAME] for r in tracing.stage_log()
    }
    assert len(asyncio.all_tasks()) == tasks0
    assert len(loop._scheduled) == timers0
    new = [t for t in set(threading.enumerate()) - threads0 if not t.name.startswith("asyncio_")]
    assert new == []


# ---------------------------------------------------------------------------
# Collections and the load monitors' loop counters
# ---------------------------------------------------------------------------


def _gc_entries() -> int:
    return gc.callbacks.count(tracing._on_gc)


async def test_the_collection_callback_is_held_while_a_monitor_runs():
    found = list(gc.callbacks)
    held = _gc_entries()
    a = LoadMonitor(interval=0.01, stall_threshold_ms=0)
    b = LoadMonitor(interval=0.01, stall_threshold_ms=0)
    assert gc.callbacks == found  # constructing a monitor installs nothing
    ta, tb = asyncio.ensure_future(a.run()), asyncio.ensure_future(b.run())
    await asyncio.sleep(0.05)
    assert _gc_entries() == 1  # two monitors, one entry
    ta.cancel()
    await asyncio.gather(ta, return_exceptions=True)
    assert _gc_entries() == 1  # the last one out removes it
    tb.cancel()
    await asyncio.gather(tb, return_exceptions=True)
    assert _gc_entries() == held == 0 and gc.callbacks == found


def test_a_full_collection_is_a_stage_and_young_ones_only_count():
    tracing.watch_gc()
    try:
        before = tracing.stage_totals()
        gc.collect(0)
        gc.collect(1)
        assert [r for r in tracing.stage_log() if r[NAME].startswith("gc.")] == []
        gc.collect()
    finally:
        tracing.unwatch_gc()
    after = tracing.stage_totals()
    for gen in ("gc.gen0", "gc.gen1", "gc.gen2"):
        assert after[gen][0] >= before.get(gen, (0, 0, 0))[0] + 1
    full = [r for r in tracing.stage_log() if r[NAME] == "gc.gen2"]
    assert len(full) >= 1
    rec = full[-1]
    assert rec[PARENT] is None and rec[CALL] == 0 and rec[T1] >= rec[T0]
    assert rec[THREAD] == threading.get_ident()
    gc.collect()  # no watcher: nothing is logged
    assert len([r for r in tracing.stage_log() if r[NAME] == "gc.gen2"]) == len(full)


class _Node:
    pass


def _cycle() -> "_Node":
    a, b = _Node(), _Node()
    a.other, b.other = b, a
    return a


@contextlib.contextmanager
def _settling():
    """The watcher on, and no collection but the test's own."""
    assert tracing._GC_WATCHERS == 0
    # (The interpreter keeps its own immortal objects there: a full
    # collection moves them back after an ``unfreeze``. 375 in 3.12.12.)
    assert gc.get_freeze_count() < 1_000
    gc.disable()
    tracing.watch_gc()
    try:
        yield
    finally:
        tracing.unwatch_gc()
        gc.enable()


def _walks() -> tuple[int, int]:
    g = tracing.gc_gauges()
    return int(g["rio.gc.settled"]), int(g["rio.gc.whole_walks"])


def test_a_full_collection_sets_its_survivors_aside_for_the_next():
    with _settling():
        settled, whole = _walks()
        gc.collect()  # nothing is settled yet: a whole walk
        assert _walks() == (settled, whole + 1)
        kept = gc.get_freeze_count()
        assert kept > 10_000  # the interpreter's modules, at the least
        assert len(gc.get_objects()) < kept // 100
        made = [_Node() for _ in range(500)]
        gc.collect()  # walks the 500 and what else is new, not the kept
        assert _walks() == (settled + 1, whole + 1)
        assert kept + 500 <= gc.get_freeze_count() <= kept + 1_000
        assert len(gc.get_objects()) < kept // 100
        g = tracing.gc_gauges()
        assert kept + 500 <= g["rio.gc.settled_objects"] <= kept + 1_000
        assert len(made) == 500


def test_what_only_a_settled_object_holds_survives_every_collection():
    holder = _Node()
    with _settling():
        gc.collect()
        holder.child = _cycle()  # young, and reachable from the settled heap only
        ref = weakref.ref(holder.child)
        gc.collect(0)
        gc.collect(1)
        gc.collect()
        assert ref() is holder.child
        gc.collect()  # the child is settled itself now
        assert ref() is holder.child
        del holder.child
        assert ref() is not None  # a cycle: not freed by reference count


def test_a_cycle_that_dies_among_settled_objects_is_found_by_the_next_whole_walk():
    with _settling():
        gc.collect()
        kept = gc.get_freeze_count()
        ring = _cycle()
        ref = weakref.ref(ring)
        gc.collect()  # the ring is settled
        del ring
        gc.collect()
        assert ref() is not None  # dead, and beyond a settled collection's reach
        settled, whole = _walks()
        grown = [[] for _ in range(kept + 1_000)]
        gc.collect()  # settles what was grown: the heap has doubled
        assert ref() is not None and _walks() == (settled + 1, whole)
        gc.collect()  # thaws first, walks everything
        assert ref() is None
        assert _walks() == (settled + 1, whole + 1)
        assert gc.get_freeze_count() >= 2 * kept  # and set aside again
        gc.collect()
        assert _walks() == (settled + 2, whole + 1)  # geometric: not again soon
        assert len(grown) == kept + 1_000


def test_a_heap_that_shrank_is_the_one_that_has_to_double():
    with _settling():
        gc.collect()
        small = gc.get_freeze_count()
        peak = [[] for _ in range(small + 1_000)]
        gc.collect()
        gc.collect()  # a whole walk at the peak: it kept twice ``small``
        settled, whole = _walks()
        assert gc.get_freeze_count() >= 2 * small
        del peak  # freed by reference count: the settled heap is small again
        passing = [[] for _ in range(2 * small + 4_000)]
        gc.collect()  # the bound passes twice the peak ...
        del passing
        gc.collect()  # ... so this one counts: not doubled, and smaller than kept
        assert _walks() == (settled + 2, whole)
        assert small <= gc.get_freeze_count() < small + 4_000
        ring = _cycle()
        ref = weakref.ref(ring)
        grown = [[] for _ in range(small + 4_000)]
        gc.collect()
        del ring
        gc.collect()  # twice ``small`` is settled, far under twice the peak: a whole walk
        assert ref() is None and _walks() == (settled + 3, whole + 1)
        assert len(grown) == small + 4_000


def test_every_full_collection_settled_or_whole_is_one_gc_gen2_record():
    with _settling():
        gc.collect()
        gc.collect()
        gc.collect(1)
        gc.collect()
        settled, whole = _walks()
    assert whole >= 1 and settled >= 2
    full = [r for r in tracing.stage_log() if r[NAME] == "gc.gen2"]
    assert len(full) == 3 == tracing.stage_totals()["gc.gen2"][0]
    assert all(r[T1] >= r[T0] and r[PARENT] is None for r in full)


def test_the_last_unwatch_gives_the_collector_back_as_it_was():
    found = list(gc.callbacks)
    tracing.watch_gc()
    tracing.watch_gc()
    try:
        assert _gc_entries() == 1
        gc.collect()
        assert gc.get_freeze_count() > 10_000
        tracing.unwatch_gc()
        assert _gc_entries() == 1 and gc.get_freeze_count() > 10_000  # still held
        gc.collect()
    finally:
        tracing.unwatch_gc()
    assert _gc_entries() == 0 and gc.callbacks == found
    assert gc.get_freeze_count() == 0
    assert tracing.gc_gauges()["rio.gc.settled_objects"] == 0.0
    tracing.unwatch_gc()  # one too many: counted, not raised
    assert tracing._GC_WATCHERS == 0
    gc.collect()  # no watcher: nothing of the program's is set aside
    assert gc.get_freeze_count() < 1_000


async def test_the_monitor_keeps_raw_lag_samples_and_times_stalls():
    m = LoadMonitor(interval=0.02, stall_threshold_ms=100.0, stall_cooldown=0.0)
    task = asyncio.ensure_future(m.run())
    try:
        t_lo = time.perf_counter_ns()
        await asyncio.sleep(0.1)
        held0 = tracing.loop_gauges()
        time.sleep(0.3)  # hold the loop: one tick wakes ~0.28 s late
        await asyncio.sleep(0.1)
        t_hi = time.perf_counter_ns()
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
    samples = list(m.stats.lag_samples)
    assert len(samples) == m.stats.samples >= 4
    assert all(t_lo <= t <= t_hi and lag >= 0.0 for t, lag in samples)
    assert [t for t, _ in samples] == sorted(t for t, _ in samples)
    worst = max(lag for _, lag in samples)
    assert 200.0 <= worst <= 400.0
    # The hold itself is the loop's own clock's to time (``rio.loop.hold.*``,
    # a 5 ms tick): the monitor's tick saw what was left of it when it was due.
    held = tracing.loop_gauges()
    assert held["rio.loop.hold.count"] >= held0["rio.loop.hold.count"] + 1
    assert held["rio.loop.hold.max_ms"] >= 290.0  # (cumulative: the process's longest ever)
    # (The loop's tick was due up to one tick after the monitor's.)
    tick_ms = tracing.LOOP_TICK_NS / 1e6
    assert held["rio.loop.hold.total_ms"] - held0["rio.loop.hold.total_ms"] >= worst - tick_ms
    longest = max(tracing.hold_log(), key=lambda h: h[1] - h[0])
    assert t_lo <= longest[0] < longest[1] <= t_hi and longest[2] == "unnamed"
    assert not hasattr(m.stats, "stall_max_ms") and not hasattr(m, "stall_gauges")
    assert m.stats.lag_samples.maxlen == 1024


async def test_server_gauges_carry_the_stages_and_the_loop_counters():
    m = LoadMonitor(interval=0.01, stall_threshold_ms=0)
    task = asyncio.ensure_future(m.run())
    try:
        with tracing.stage("place.apply"):
            pass
        await asyncio.sleep(0.05)
        gc.collect()
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
    gauges = server_gauges(types.SimpleNamespace(load_monitor=m))
    for field in ("count", "total_ms", "max_ms"):
        assert f"rio.stage.place.apply.{field}" in gauges
        assert f"rio.stage.gc.gen2.{field}" in gauges
    assert gauges["rio.stage.place.apply.count"] == 1.0
    assert gauges["rio.stage.gc.gen2.count"] >= 1.0
    for field in ("stalls", "loop_lag_ms", "samples"):
        assert f"rio.load.{field}" in gauges
    for field in ("ticks", "late_ms", "hold.count", "hold.total_ms", "hold.max_ms"):
        assert f"rio.loop.{field}" in gauges
    assert gauges["rio.loop.ticks"] >= 5.0  # 50 ms of a 5 ms tick
    assert "rio.load.stall_max_ms" not in gauges and "rio.load.stall_total_ms" not in gauges
    for field in ("settled", "whole_walks", "settled_objects"):
        assert f"rio.gc.{field}" in gauges
    assert gauges["rio.gc.settled"] + gauges["rio.gc.whole_walks"] >= 1.0
    assert all(isinstance(v, float) for v in gauges.values())


# ---------------------------------------------------------------------------
# The saves' RED row
# ---------------------------------------------------------------------------


@message
class Tally:
    n: int = 0


class Counter(ServiceObject):
    tally = managed_state(Tally)


async def test_save_state_shows_in_the_red_rows_with_an_exact_count():
    ctx = AppData()
    registry = MetricsRegistry()
    ctx.set(registry)
    ctx.set(LocalState(), as_type=StateProvider)
    obj = Counter()
    obj.id = "c1"
    for i in range(20):
        obj.tally.n = i
        await save_state(obj, ctx)
    rows = {(r[0], r[1]): r for r in registry.snapshot_rows()}
    row = rows[SAVE_METRIC_KEY]
    assert row[2] == 20  # every save counted
    assert sum(row[5]) == 3  # saves 1, 9 and 17 timed: the 1-in-8 stride
    assert "rio.handler.rio.State.save.count" in registry.gauges()
    # Without a registry the save is a plain save.
    bare = AppData()
    bare.set(LocalState(), as_type=StateProvider)
    await save_state(obj, bare)
    assert bare.try_get(StateProvider).count() == 1


async def test_saves_in_flight_together_are_still_timed_one_in_eight():
    gate = asyncio.Event()

    class Slow(LocalState):
        async def save(self, *a):
            await gate.wait()
            return await super().save(*a)

    ctx = AppData()
    registry = MetricsRegistry()
    ctx.set(registry)
    ctx.set(Slow(), as_type=StateProvider)
    objs = []
    for i in range(32):
        obj = Counter()
        obj.id = f"c{i}"
        objs.append(obj)
    saves = [asyncio.ensure_future(save_state(o, ctx)) for o in objs]
    await asyncio.sleep(0)  # all 32 entered, none done
    assert registry.get(*SAVE_METRIC_KEY).count == 32
    gate.set()
    await asyncio.gather(*saves)
    hist = registry.get(*SAVE_METRIC_KEY)
    assert hist.count == 32 and sum(hist.buckets) == 4


async def test_a_failing_save_is_still_counted():
    class Broken(LocalState):
        async def save(self, *a):
            raise OSError("disk")

    ctx = AppData()
    registry = MetricsRegistry()
    ctx.set(registry)
    ctx.set(Broken(), as_type=StateProvider)
    obj = Counter()
    obj.id = "c2"
    for _ in range(9):
        with pytest.raises(OSError):
            await save_state(obj, ctx)
    assert registry.get(*SAVE_METRIC_KEY).count == 9
