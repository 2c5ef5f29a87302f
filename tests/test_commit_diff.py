"""A full solve commits what its worker thread already compared (PR 41):
``_commit_diff`` takes the movers' positions, the plan's seats a node (the
snapshot's, less what the movers leave, plus what they take) and, where a
sink takes the plan, the ordered move list in the thread that ran the solve,
``_DIFF_SLICE_ROWS`` rows a call, and the commit's lock hold is left with the
epoch check, O(movers) and O(nodes). What is committed is what one
``np.nonzero`` and one ``np.bincount`` over the whole arrays give.
"""

import asyncio
import sys
import threading

import numpy as np
import pytest

from rio_tpu import ObjectId, ObjectPlacementItem
from rio_tpu.object_placement import jax_placement as jp
from tests.test_snapshot_slices import NODES, _directory  # rows on 8 of 16 nodes: a solve moves some

N = 3000


def _gauges(p) -> dict[str, float]:
    prefix = "rio.solve.diff."
    return {k[len(prefix):]: v for k, v in p.place_gauges().items() if k.startswith(prefix)}


def _watch_the_diff(monkeypatch, after=None) -> list:
    """Every ``_commit_diff`` call's arguments, copied, and the thread it ran
    on; ``after`` runs in that thread once the diff is made."""
    seen, real = [], jp._commit_diff

    def diff(assignment, cur_idx, seats):
        out = real(assignment, cur_idx, seats)
        seen.append((np.array(assignment), np.array(cur_idx), np.array(seats), threading.get_ident()))
        if after is not None:
            after()
        return out

    monkeypatch.setattr(jp, "_commit_diff", diff)
    return seen


def _two_level(monkeypatch) -> dict:
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    return {"mode": "sinkhorn", "n_iters": 10}


@pytest.mark.parametrize("route", ["flat", "two_level"])
@pytest.mark.parametrize("slice_rows", [N, 500], ids=["one_call", "six_slices"])
@pytest.mark.parametrize("sink", [False, True], ids=["raw_writes", "move_sink"])
async def test_the_commit_is_what_the_whole_arrays_give(sink, slice_rows, route, monkeypatch):
    monkeypatch.setattr(jp, "_DIFF_SLICE_ROWS", slice_rows)
    p = _directory(N, **(_two_level(monkeypatch) if route == "two_level" else {}))
    seen = _watch_the_diff(monkeypatch)
    plans = []

    async def move_sink(planned):
        plans.append(planned)
        return len(planned)

    before = list(p._placements.items())
    moved = await p.rebalance(delta=False, move_sink=move_sink if sink else None)
    assert p.stats.mode == {"flat": "greedy", "two_level": "sinkhorn+hier_at_scale+mesh_chunk"}[route]
    (assignment, cur_idx, seats, thread), = seen
    assert thread != threading.get_ident()
    assert seats.tolist() == np.bincount(cur_idx, minlength=p._node_axis).tolist()
    # The plain reference: one pass over the whole arrays, one Python loop.
    movers = np.nonzero(assignment != cur_idx)[0]
    assert moved == p.stats.moved == len(movers) > 0 and not p.stats.discarded
    counts = np.bincount(assignment, minlength=p._node_axis)
    assert p._plan.seat_counts.dtype == counts.dtype
    assert p._plan.seat_counts.tolist() == counts.tolist() and p._plan.epoch == p._epoch
    keys = [k for k, _ in before]
    if sink:
        want = [(keys[i], NODES[cur_idx[i]], NODES[assignment[i]]) for i in movers.tolist()]
        want.sort(key=lambda m: (m[1], m[2]))
        assert plans == [want]
        assert list(p._placements.items()) == before  # a row flips when its hand-off commits
    else:
        assert plans == []
        assert list(p._placements) == keys
        assert list(p._placements.values()) == assignment.tolist()
        assert [len(p._by_node.get(i, ())) for i in range(16)] == counts[:16].tolist()
    assert _gauges(p)["slices"] == (1 if slice_rows == N else 6)


@pytest.mark.parametrize("n, share", [(1, 1), (1000, 10), (1001, 10), (1499, 10), (2001, 10), (4000, 10), (2001, 1)],
                         ids=["a_row", "two_slices", "two_slices_and_a_row", "a_short_last_slice",
                              "four_slices_and_a_row", "eight_slices", "every_row_moves"])
def test_the_sliced_diff_is_the_one_call_diff(n, share, monkeypatch):
    monkeypatch.setattr(jp, "_DIFF_SLICE_ROWS", 500)
    rng = np.random.default_rng(n)
    cur_idx = rng.integers(0, 13, n).astype(np.int32)
    assignment = cur_idx.copy()
    movers = np.arange(n) if share == 1 else rng.integers(0, n, n // share + 1)
    assignment[movers] = (assignment[movers] + 1 + rng.integers(0, 3, len(movers))) % 19
    assignment[-1] = 18 if n > 1 else assignment[-1]  # past the node axis: the counts grow with it
    pos, counts, slices = jp._commit_diff(assignment, cur_idx, np.bincount(cur_idx, minlength=16))
    want_pos, want_counts = np.nonzero(assignment != cur_idx)[0], np.bincount(assignment, minlength=16)
    assert slices == (1 if n <= 1000 else -(-n // 500))
    assert pos.dtype == want_pos.dtype and pos.tolist() == want_pos.tolist()
    assert counts.dtype == want_counts.dtype and counts.tolist() == want_counts.tolist()


@pytest.mark.parametrize("sink", [False, True], ids=["raw_writes", "move_sink"])
async def test_a_writer_between_the_solve_and_the_commit_discards_the_diff(sink, monkeypatch):
    monkeypatch.setattr(jp, "_DIFF_SLICE_ROWS", 500)
    p = _directory(N)
    loop = asyncio.get_running_loop()

    def a_write_lands():  # in the worker thread, the diff in hand: the loop runs the writer
        if len(seen) == 1:  # the first attempt's only
            item = ObjectPlacementItem(ObjectId("R", "5"), NODES[9])
            asyncio.run_coroutine_threadsafe(p.update(item), loop).result(30)

    seen = _watch_the_diff(monkeypatch, after=a_write_lands)
    plans = []

    async def move_sink(planned):
        plans.append(planned)
        return len(planned)

    rows, epoch = dict(p._placements), p._epoch
    assert await p.rebalance(delta=False, move_sink=move_sink if sink else None) == 0
    assert len(seen) == 1 and (seen[0][0] != seen[0][1]).any()  # there was a diff to apply
    assert p.stats.discarded and p.stats.moved == 0 and p._plan is None and plans == []
    assert p._epoch == epoch + 1  # the writer's, not a commit's
    assert dict(p._placements) == {**rows, "R.5": 9}
    assert _gauges(p) == {"commits": 0, "rows": 0, "slices": 0, "busy_ms": 0}
    # The next attempt finds nobody in its way and commits its own diff.
    assert await p.rebalance(delta=False, move_sink=move_sink if sink else None) > 0
    assert not p.stats.discarded and _gauges(p)["commits"] == 1


class _Watched(np.ndarray):
    """An array whose every ufunc (a comparison among them) is recorded with
    its thread and the longest operand."""

    calls: list = []

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        plain = [np.asarray(x) if isinstance(x, _Watched) else x for x in inputs]
        if "out" in kwargs:
            kwargs["out"] = tuple(np.asarray(x) if isinstance(x, _Watched) else x for x in kwargs["out"])
        _Watched.calls.append((ufunc.__name__, threading.get_ident(), max(np.size(x) for x in plain)))
        return getattr(ufunc, method)(*plain, **kwargs)


def _record_the_passes(monkeypatch) -> list:
    """``np.nonzero``, ``np.flatnonzero`` and ``np.bincount`` record
    ``(name, thread, rows)``; so does every ufunc over a ``_Watched``."""
    calls = _Watched.calls = []
    for name in ("nonzero", "flatnonzero", "bincount"):
        def passing(a, *args, _real=getattr(np, name), _name=name, **kwargs):
            calls.append((_name, threading.get_ident(), np.size(a)))
            return _real(a, *args, **kwargs)

        monkeypatch.setattr(np, name, passing)
    return calls


@pytest.mark.parametrize("slice_rows", [N, 500], ids=["one_call", "six_slices"])
@pytest.mark.parametrize("sink", [False, True], ids=["raw_writes", "move_sink"])
async def test_no_pass_over_the_directory_runs_on_the_loop_under_the_commits_lock(
    sink, slice_rows, monkeypatch
):
    monkeypatch.setattr(jp, "_DIFF_SLICE_ROWS", slice_rows)
    p = _directory(N)
    snapshot, held = p._snapshot, []

    async def watched_snapshot(delta):
        *head, cur_idx = await snapshot(delta)
        return (*head, cur_idx.view(_Watched))

    class Lock(asyncio.Lock):  # which passes ran while the directory's lock was held
        async def acquire(self):
            await super().acquire()
            held.append(len(calls))

        def release(self):
            held.append(len(calls))
            super().release()

    monkeypatch.setattr(p, "_snapshot", watched_snapshot)
    p._lock = Lock()
    calls = _record_the_passes(monkeypatch)

    async def move_sink(planned):
        return len(planned)

    loop_thread = threading.get_ident()
    assert await p.rebalance(delta=False, move_sink=move_sink if sink else None) > 0
    assert not p.stats.discarded
    # The recorder sees the passes: the worker thread compared every row and counted the movers.
    off_loop = [(what, rows) for what, thread, rows in calls if thread != loop_thread]
    assert sum(rows for what, rows in off_loop if what == "not_equal" and rows <= slice_rows) >= N, calls
    assert [rows for what, rows in off_loop if what == "bincount" and rows == p.stats.moved], calls
    *_, (a, b) = zip(held[::2], held[1::2])  # the commit is the call's last hold
    assert [c for c in calls[a:b] if c[2] >= N] == []
    # No such pass on the loop's thread anywhere in the call, held or not.
    assert [c for c in calls if c[1] == loop_thread and c[2] >= N] == []


def test_no_call_passes_over_more_than_a_slice_and_another_thread_runs_between_two(monkeypatch):
    step = jp._DIFF_SLICE_ROWS
    n = 4 * step + 5
    rng = np.random.default_rng(41)
    cur_idx = rng.integers(0, 1003, n).astype(np.int32)
    assignment = cur_idx.copy()
    assignment[: step + 7] += 1  # more movers than a slice holds: they are counted in slices too
    assignment[rng.integers(0, n, 64)] = 7
    want = np.nonzero(assignment != cur_idx)[0], np.bincount(assignment, minlength=1024)
    seats = np.bincount(cur_idx, minlength=1024)
    calls = _record_the_passes(monkeypatch)
    turns, stop, at_call = [0], threading.Event(), []
    find = np.flatnonzero

    def finding(a, *args, **kwargs):
        at_call.append(turns[0])
        return find(a, *args, **kwargs)

    monkeypatch.setattr(np, "flatnonzero", finding)

    def other_thread():
        while not stop.is_set():
            turns[0] += 1

    interval = sys.getswitchinterval()
    thread = threading.Thread(target=other_thread)
    sys.setswitchinterval(1e-5)
    try:
        thread.start()
        pos, counts, slices = jp._commit_diff(assignment, cur_idx.view(_Watched), seats)
    finally:
        sys.setswitchinterval(interval)
        stop.set()
        thread.join(30)
    assert not thread.is_alive()
    assert slices == 5 and pos.tolist() == want[0].tolist() and counts.tolist() == want[1].tolist()
    assert {"not_equal", "flatnonzero", "bincount"} <= {what for what, _, _ in calls}
    assert max(rows for _, _, rows in calls) == step
    assert sorted(rows for what, _, rows in calls if what == "not_equal") == [5, step, step, step, step]
    assert len(want[0]) > step and len([c for c in calls if c[0] == "bincount"]) == 4
    # Between the first slice and the last the interpreter handed the lock over.
    assert len(at_call) == 5 and at_call[-1] > at_call[0]


@pytest.mark.parametrize("slice_rows", [N, 500], ids=["one_call", "six_slices"])
async def test_the_gauges_count_the_commits_their_rows_and_the_slices(slice_rows, monkeypatch):
    monkeypatch.setattr(jp, "_DIFF_SLICE_ROWS", slice_rows)
    p = _directory(N)
    assert _gauges(p) == {"commits": 0, "rows": 0, "slices": 0, "busy_ms": 0}
    assert await p.rebalance(delta=False) > 0
    slices = 1 if slice_rows == N else 6
    g = _gauges(p)
    busy = g.pop("busy_ms")
    assert busy > 0 and g == {"commits": 1, "rows": N, "slices": slices}
    # The O(displaced) route commits through its own block: no diff of N rows.
    p.sync_members(NODES[:-1])
    assert await p.rebalance() > 0 and p.stats.mode.endswith("+delta")
    assert _gauges(p) == {**g, "busy_ms": busy}
    await p.rebalance(delta=False)
    g = _gauges(p)
    assert g.pop("busy_ms") > busy and g == {"commits": 2, "rows": 2 * N, "slices": 2 * slices}
    # The stage the hold log would name, on the worker's thread, outside ``solve.device``.
    from rio_tpu import tracing

    diffs = [r for r in tracing.stage_log() if r[0] == "solve.diff"][-1]
    device = [r for r in tracing.stage_log() if r[0] == "solve.device"][-1]
    assert diffs[3] == "solve.full" and diffs[5] == device[5] != threading.get_ident()
    assert device[2] <= diffs[1]
