"""A full solve's snapshot read in slices (PR 38): ``_snapshot`` takes the
directory's keys and seats ``_SNAPSHOT_SLICE_ROWS`` rows a lock hold and lets
the event loop turn between two, and what it hands the solve is the cut one
hold would have read: the same key objects in the same order, the same seats,
one epoch. A writer between two slices restarts the read; the third read of
a call takes one hold.
"""

import ast
import asyncio
import inspect

import numpy as np
import pytest

from rio_tpu import ObjectId, ObjectPlacementItem
from rio_tpu.object_placement import jax_placement as jp
from rio_tpu.object_placement import persistent
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

S = jp._SNAPSHOT_SLICE_ROWS
NODES = [f"10.38.0.{i + 1}:7000" for i in range(16)]


def _directory(n, **kwargs) -> JaxObjectPlacement:
    """``n`` rows over 16 nodes, all seated on the first 8: a full solve has
    rows to move. Seated through ``_apply_chunk``, the program's own bulk
    write (epoch bump included), without a device solve a test."""
    p = JaxObjectPlacement(**{"mode": "greedy", **kwargs})
    p.sync_members(NODES)
    p._apply_chunk([f"R.{i}" for i in range(n)], (np.arange(n) * 7919 % 8).astype(np.int32))
    return p


def _gauges(p) -> dict[str, float]:
    prefix = "rio.place.snapshot."
    return {k[len(prefix):]: v for k, v in p.place_gauges().items() if k.startswith(prefix)}


def _is_the_directory(p, snap) -> None:
    """``snap`` is the directory as it stands: its own key objects, in its
    order, their seats, and its epoch."""
    n, epoch, *_, keys, cur_idx = snap
    assert n == len(p._placements) == len(keys) and epoch == p._epoch
    assert all(a is b for a, b in zip(keys, p._placements))
    assert cur_idx.dtype == np.int32
    assert cur_idx.tolist() == list(p._placements.values())


async def _between_slices(p, when, write) -> asyncio.Task:
    """A task that calls ``write`` once ``when(gauges)`` holds: it runs in
    the turns of the loop that the sliced read leaves between two holds."""
    async def writer():
        while not when(_gauges(p)):
            await asyncio.sleep(0)
        await write()

    task = asyncio.ensure_future(writer())
    await asyncio.sleep(0)
    return task


@pytest.mark.parametrize("n", [2 * S, 2 * S + 1, 3 * S + 1, 4 * S],
                         ids=["two_slices", "two_slices_and_a_row", "three_slices_and_a_row", "four_slices"])
async def test_the_sliced_read_is_the_one_hold_read(n, monkeypatch):
    p = _directory(n)
    sliced = await p._snapshot(False)
    _is_the_directory(p, sliced)
    slices = -(-n // S)
    g = _gauges(p)
    assert (g.pop("busy_ms") > 0) == (slices > 2)
    assert g == {"sliced": slices > 2, "slices": slices if slices > 2 else 0, "restarts": 0, "whole": 0}
    moved = await p.rebalance(delta=False)
    # The parent's route: the same directory read in one hold.
    q = _directory(n)
    monkeypatch.setattr(jp, "_SNAPSHOT_SLICE_ROWS", n)
    whole = await q._snapshot(False)
    assert _gauges(q)["sliced"] == 0
    assert whole[-2] == sliced[-2] and np.array_equal(whole[-1], sliced[-1])
    assert await q.rebalance(delta=False) == moved > 0
    assert list(q._placements.items()) == list(p._placements.items())
    assert not p.stats.discarded and p.stats.mode == q.stats.mode


async def test_the_loop_turns_between_two_slices():
    p = _directory(4 * S + 1)
    loop = asyncio.get_running_loop()
    turns, reading = [0], [True]

    def turn():
        turns[0] += 1
        if reading[0]:
            loop.call_soon(turn)

    loop.call_soon(turn)
    snap = await p._snapshot(False)
    reading[0] = False
    _is_the_directory(p, snap)
    assert _gauges(p)["slices"] == 5 and turns[0] >= 4
    assert not p._lock.locked()


async def _an_update(p):
    await p.update(ObjectPlacementItem(ObjectId("R", "5"), NODES[9]))  # size stands, a seat moves


async def _a_remove(p):
    await p.remove(ObjectId("R", "7"))


async def _an_assign_batch(p):
    await p.assign_batch([ObjectId("R", "new-1"), ObjectId("R", "new-2")])


@pytest.mark.parametrize("write", [_an_update, _a_remove, _an_assign_batch],
                         ids=lambda f: f.__name__.strip("_"))
async def test_a_writer_between_two_slices_restarts_the_read(write):
    p = _directory(3 * S + 1)
    task = await _between_slices(p, lambda g: g["slices"] >= 2, lambda: write(p))
    before = p._epoch
    snap = await p._snapshot(False)
    await asyncio.wait_for(task, 30)
    assert p._epoch > before
    _is_the_directory(p, snap)  # at the final epoch, with the writer's row
    g = _gauges(p)
    assert g["restarts"] == 1 and g["sliced"] == 1 and g["whole"] == 0
    # Two slices of the read that was dropped, and the read that was kept.
    assert g["slices"] == 2 + -(-len(p._placements) // S)


async def test_under_a_writer_after_every_slice_the_third_read_is_whole_and_the_plan_commits():
    p = _directory(3 * S + 1)

    async def writer():
        seen, i = 0, 0
        while not (g := _gauges(p))["whole"]:
            if g["slices"] > seen:
                seen, i = g["slices"], i + 1
                await p.update(ObjectPlacementItem(ObjectId("R", str(i)), NODES[10]))
            await asyncio.sleep(0)
        return i

    task = asyncio.ensure_future(writer())
    moved = await p.rebalance(delta=False)
    assert await asyncio.wait_for(task, 30) == 2
    g = _gauges(p)
    assert g.pop("busy_ms") > 0
    assert g == {"sliced": 0, "slices": 2, "restarts": 2, "whole": 1}
    assert moved > 0 and not p.stats.discarded and p.stats.n_objects == 3 * S + 1
    loads = np.bincount(list(p._placements.values()), minlength=16)
    assert loads.max() - loads.min() <= 2  # the plan it committed is a plan: 8 nodes were empty


async def test_the_kept_identities_are_reused_after_a_sliced_snapshot(monkeypatch):
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    monkeypatch.setattr(jp, "_SNAPSHOT_SLICE_ROWS", 500)
    n = 3000
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10)
    p.sync_members(NODES)
    await p.assign_batch([ObjectId("R", str(i)) for i in range(n)])
    await p.rebalance(delta=False)
    assert p.stats.mode == "sinkhorn+hier_at_scale+mesh_chunk"
    first = p.place_gauges()
    assert first["rio.solve.features.rows_hashed"] == n
    await p.rebalance(delta=False)
    second = p.place_gauges()
    assert second["rio.solve.features.rows_hashed"] == n  # none hashed again
    assert second["rio.solve.features.rows_reused"] == n
    assert second["rio.place.snapshot.sliced"] == 2 and second["rio.place.snapshot.slices"] == 12


async def test_a_directory_of_two_slices_and_the_fast_path_read_no_slice(monkeypatch):
    p = _directory(2 * S)
    await p.rebalance(delta=False)
    assert _gauges(p) == {"sliced": 0, "slices": 0, "restarts": 0, "whole": 0, "busy_ms": 0}
    # The O(displaced) route: a member leaves under a committed plan. Even
    # where an O(N) read WOULD slice, it reads none of the directory.
    monkeypatch.setattr(jp, "_SNAPSHOT_SLICE_ROWS", 500)
    p.sync_members(NODES[:-1])
    reads = []
    monkeypatch.setattr(p, "_read_rows", lambda *a: reads.append(a))
    assert await p.rebalance() > 0 and p.stats.mode.endswith("+delta")
    assert reads == [] and _gauges(p)["sliced"] == 0


# ---- The invariant the restart rule rests on: nobody writes ``_placements``
# but the three seams, and every public writer moves ``_epoch``. (That each
# seam's lock hold ends with the epoch moved is
# ``test_every_writer_of_seats_moves_the_epoch_in_the_hold_it_wrote_in``.)

_MUTATING = {"pop", "popitem", "update", "clear", "setdefault", "__setitem__", "__delitem__"}


def _is_the_mirror(node) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == "_placements"


def _writers_of_the_mirror(cls) -> set[str]:
    found = set()
    for fn in ast.walk(ast.parse(inspect.getsource(inspect.getmodule(cls)))):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            targets = []
            if isinstance(node, (ast.Assign, ast.Delete)):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                  and node.func.attr in _MUTATING and _is_the_mirror(node.func.value)):
                found.add(fn.name)
            for t in targets:
                if _is_the_mirror(t) or (isinstance(t, ast.Subscript) and _is_the_mirror(t.value)):
                    found.add(fn.name)
    return found


def test_only_the_three_seams_write_the_mirror():
    assert _writers_of_the_mirror(JaxObjectPlacement) - {"__init__"} == {
        "_set_placement", "_drop_placement", "_seat_new",
    }
    # (The durable subclass overrides the seams and writes through ``super()``.)
    assert _writers_of_the_mirror(persistent.PersistentJaxObjectPlacement) == set()


async def _a_clean_server(p):
    await p.clean_server(NODES[3])


async def _a_promotion(p):
    oid = ObjectId("R", "11")
    epoch = await p.set_standbys(oid, [NODES[12]])
    assert await p.promote_standby(oid, NODES[12], epoch) == epoch + 1


async def _an_update_to_none(p):
    await p.update(ObjectPlacementItem(ObjectId("R", "13"), None))


async def _a_full_solve(p):
    assert await p.rebalance(delta=False) > 0


async def _a_delta_solve(p):
    assert await p.rebalance() > 0 and p.stats.mode.endswith("+delta")


@pytest.mark.parametrize("write", [
    _an_update, _an_update_to_none, _a_remove, _an_assign_batch, _a_clean_server,
    _a_promotion, _a_full_solve, _a_delta_solve,
], ids=lambda f: f.__name__.strip("_"))
async def test_every_change_of_the_mirror_moves_the_epoch(write):
    p = _directory(400)
    if write is _a_delta_solve:  # a member leaves under a committed plan
        await p.rebalance(delta=False)
        p.sync_members(NODES[:-1])
    rows, epoch = list(p._placements.items()), p._epoch
    await write(p)
    assert list(p._placements.items()) != rows, "the scenario changed no row: it proves nothing"
    assert p._epoch > epoch
