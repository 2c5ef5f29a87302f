"""Sustained member churn on the default wiring (BASELINE config 4 played).

* hand-offs over a cluster whose members come and go keep the process's
  descriptors bounded (connections pooled per process and address);
* the churn cell of the benchmark, rehearsed end to end, and the churn at
  larger sizes on the CPU with the load monitors ON (``benchmark/`` holds the
  cell's own tests; these guard it from the suite the driver runs).

Every run of the cell binds its members at fixed loopback addresses
(``127.77.x.y:7000``), so these tests stay in ONE file: the suite runs a
file's tests in one worker, one after the other.
"""

import asyncio
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from rio_tpu import (
    AppData,
    Client,
    LocalStorage,
    ObjectId,
    Registry,
    Server,
    ServiceObject,
    handler,
    message,
)
from rio_tpu.cluster.membership_protocol import LocalClusterProvider
from rio_tpu.migration import open_connections
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

REPO = Path(__file__).resolve().parents[1]
CELL = "presence-1m-1k-churn.heartbeat-churn"
MESH_CELL = "presence-4m-1k-mesh.resolve"


@message
class Touch:
    n: int = 1


@message
class Touched:
    n: int = 0


class Held(ServiceObject):
    """Volatile state that a hand-off carries: prefetch and install run."""

    def __init__(self):
        self.n = 0

    @handler
    async def touch(self, msg: Touch, ctx: AppData) -> Touched:
        self.n += msg.n
        return Touched(n=self.n)

    def __migrate_state__(self):
        return {"n": self.n}

    def __restore_state__(self, state):
        self.n = state["n"]


def _descriptors() -> int:
    return len(os.listdir("/proc/self/fd"))


def test_descriptors_do_not_grow_with_the_number_of_churn_events():
    """64 members, 20 events (4 leave, then the same 4 rejoin, ten times),
    every plan actuated by another member's coordinator: a rejoin moves rows
    from every survivor to 4 fresh targets, each a new ``Server`` with a new
    manager. One client a manager (the parent's) opens a bundle to every
    source from every target that ever existed; lanes shared by the process
    hold one bundle an address whatever the number of events."""

    async def run():
        storage = LocalStorage()
        placement = JaxObjectPlacement(mode="greedy")
        nodes: dict[str, tuple] = {}

        async def boot(address: str = "127.0.0.1:0") -> str:
            s = Server(
                address=address, registry=Registry().add_type(Held),
                cluster_provider=LocalClusterProvider(storage),
                object_placement_provider=placement, load_monitor=False,
            )
            await s.prepare()
            await s.bind()
            nodes[s.local_address] = (s, asyncio.create_task(s.run()))
            return s.local_address

        async def stop(address: str) -> None:
            _, task = nodes.pop(address)
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

        async def solve(k: int) -> int:
            for _ in range(100):
                if {m.address for m in await storage.active_members()} == set(nodes):
                    break
                await asyncio.sleep(0.01)
            placement.sync_members(await storage.members())
            sink = list(nodes.values())[k % len(nodes)][0].migration_manager.apply_moves
            return await placement.rebalance(move_sink=sink)

        addresses = [await boot() for _ in range(64)]
        await solve(0)
        ids = [ObjectId("Held", str(i)) for i in range(2048)]
        await placement.assign_batch(ids)
        await placement.rebalance(delta=False)

        async def resolver(t, i):
            return await placement.lookup(ObjectId(t, i))

        client = Client(storage, placement_resolver=resolver)
        for i in range(0, 2048, 8):
            await client.send(Held, str(i), Touch(), returns=Touched)
        seen, moved = [], 0
        for k in range(10):
            gone = addresses[4 * k % 64: 4 * k % 64 + 4]
            for a in gone:
                await stop(a)
            moved += await solve(2 * k)
            for a in gone:
                await boot(a)
            moved += await solve(2 * k + 1)
            seen.append((_descriptors(), open_connections()))
        # One activation an object, and the survivors' state moved with it.
        acks = [await client.send(Held, str(i), Touch(), returns=Touched)
                for i in range(0, 2048, 8)]
        client.close()
        live = [o for s, _ in nodes.values() for o in s.registry.object_ids()
                if o.type_name == "Held"]
        for _, task in nodes.values():
            task.cancel()
        await asyncio.gather(*(t for _, t in nodes.values()), return_exceptions=True)
        return seen, moved, acks, live

    seen, moved, acks, live = asyncio.run(asyncio.wait_for(run(), 240))
    assert moved > 20 * 100  # every rejoin drew rows from every survivor
    fds = [f for f, _ in seen]
    # Flat after the first events, and bounded by the addresses, not the events:
    # 64 listeners, at most 2 sockets a lane an address, both ends in-process.
    assert max(fds[2:]) <= fds[1] + 64, seen
    assert max(fds) < 64 * (1 + 2 * 2 * 2) + 200, seen
    assert max(c for _, c in seen) <= 64 * 2 * 2, seen
    # A row moved off a survivor took its count along; one on a member that
    # died starts again (a death loses volatile state); nothing counts twice.
    assert {a.n for a in acks} <= {1, 2} and sum(a.n == 2 for a in acks) > len(acks) // 3
    assert len(live) == len({o.id for o in live})


def test_a_full_solve_between_deltas_moves_what_the_event_displaced_and_no_more():
    """Every ninth churn solve goes full (``max_delta_solves``). The delta
    route and the device's quota repair must award a tied remainder unit to
    the node that holds it NOW: awarded by index on one side and by the
    rounded plan's occupancy on the other, each alternation moved a row for
    every unit placed differently (on the chip at 1,048,576 x 1,024 some
    900 single-row hand-offs at the full solve and as many undone by the
    next delta, an event of ten seconds)."""

    class M:
        def __init__(self, a, active):
            self.address, self.active = a, active

    async def run():
        import numpy as np

        n_obj, n_nodes = 20011, 64  # 20011 / 61: a remainder, so units tie
        p = JaxObjectPlacement(mode="sinkhorn")
        nodes = [f"10.0.0.{i}:7000" for i in range(n_nodes)]
        rng = np.random.default_rng(5)
        down = set(rng.choice(n_nodes, 3, replace=False).tolist())
        back = list(down)
        p.sync_members([M(a, i not in down) for i, a in enumerate(nodes)])
        await p.assign_batch([ObjectId("T", str(i)) for i in range(n_obj)])
        await p.rebalance(delta=False)
        seen = []
        for _ in range(11):
            leaver = int(rng.choice([i for i in range(n_nodes) if i not in down]))
            down = (down | {leaver}) - {back.pop(0)}
            back.append(leaver)
            p.sync_members([M(a, i not in down) for i, a in enumerate(nodes)])
            displaced = len(p._by_node.get(leaver, ()))
            moved = await p.rebalance()
            seen.append((p.stats.mode, moved - displaced))
        return seen

    seen = asyncio.run(asyncio.wait_for(run(), 240))
    assert [m for m, _ in seen].count("sinkhorn+collapsed") == 1, seen  # the ninth
    assert all(extra == 0 for _, extra in seen), seen


def test_a_plan_over_interchangeable_rows_is_re_expressed_with_the_fewest_moves():
    import numpy as np

    from rio_tpu.object_placement.jax_placement import _cancel_transit

    # By hand: node 0 dies (rows 0, 1), node 3 is empty. The plan sends node
    # 0's rows to nodes 1 and 2 and a row each of theirs on to node 3: four
    # moves for two displaced rows. Re-expressed: node 0's rows go to node 3.
    cur = np.array([0, 0, 1, 1, 2, 2], np.int32)
    plan = np.array([1, 2, 3, 1, 3, 2], np.int32)
    out = _cancel_transit(plan, cur)
    assert out.tolist() == [3, 3, 1, 1, 2, 2]
    rng = np.random.default_rng(0)
    for _ in range(200):
        m, n = int(rng.integers(2, 12)), int(rng.integers(1, 200))
        cur = rng.integers(0, m, n).astype(np.int32)
        plan = rng.integers(0, m, n).astype(np.int32)
        out = _cancel_transit(plan, cur)
        # Every node ends on the load the plan gave it; no row moves that the
        # plan left alone; no node both loses and receives; so the moves are
        # the net inflow, the least any plan with these loads can make.
        assert (np.bincount(out, minlength=m) == np.bincount(plan, minlength=m)).all()
        moved = out != cur
        assert (moved <= (plan != cur)).all()
        lost = np.bincount(cur[moved], minlength=m)
        got = np.bincount(out[moved], minlength=m)
        assert (np.minimum(lost, got) == 0).all()
        net = np.bincount(plan, minlength=m) - np.bincount(cur, minlength=m)
        assert moved.sum() == np.maximum(net, 0).sum()


def _cell(args, cwd=REPO, timeout=420, **more_env):
    cmd = [sys.executable, str(Path(cwd) / "benchmark" / "run.py"), *args, "--rehearse-on-cpu"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(cmd, cwd=cwd, env={**env, "JAX_PLATFORMS": "cpu", **more_env},
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(x) for x in p.stdout.strip().splitlines()]


def _served(lines) -> dict:
    return {x["check"]: x for x in lines if "check" in x}


_REHEARSED: dict = {}


def _rehearsed(cell: str) -> list:
    """The lines of the cell's one traced rehearsal (both cells bind the same
    loopback addresses: one run each, one after the other, in this file)."""
    if cell not in _REHEARSED:
        if cell == CELL:
            _REHEARSED[cell] = _cell(
                ["--workload", CELL, "--seed", "2147483777", "--seconds", "7", "--trace", "1"])
        else:
            _REHEARSED[cell] = _cell(
                ["--workload", cell, "--seed", "2147484001", "--seconds", "8", "--trace", "1"],
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                RIO_TPU_FLAT_REBALANCE_MAX_ROWS="1024", RIO_TPU_HIER_CHUNK_ROWS="1024",
            )
    return _REHEARSED[cell]


def test_the_churn_cell_rehearses_end_to_end():
    lines = _rehearsed(CELL)
    last, checks = lines[-1], _served(lines)
    assert last["correct"] is True, [x for x in lines if x.get("ok") is False]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert checks["churn.events_unserved"]["value"] == 0
    assert checks["churn.open_descriptors"]["ok"] and checks["compiles_in_window"]["value"] == 0
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert {"reseat_ms", "daemon_wait_ms", "solve_exec_ms.delta", "solve_apply_ms.delta",
            "handoff_ms.churn", "moved_per_displaced", "solves_discarded_per_event",
            "derate_steps_per_s"} <= set(m)
    assert 1.0 <= m["moved_per_displaced"] <= 1.25
    assert m["solves_discarded_per_event"] <= 1 and m["derate_steps_per_s"] < 0.5
    summary = next(x["summary"] for x in lines if "summary" in x)
    assert summary["daemon_rebalances_in_window"]["rebalances"] >= 3  # one an event


def test_the_four_chip_cell_rehearses_on_the_mesh_route():
    """``presence-4m-1k-mesh.resolve`` (PR 34) binds its members at the churn
    cell's addresses, so its rehearsal lives in this file too. The
    environment makes 8,192 rows take the chip's route: 4 devices x 2 chunks;
    the directory is built with no ``mesh=``."""
    lines = _rehearsed(MESH_CELL)
    last, checks = lines[-1], _served(lines)
    assert last["correct"] is True, [x for x in lines if x.get("ok") is False]
    assert last["attempted"] > 0 and last["failed"] == 0
    replans = [x["replan"] for x in lines if "replan" in x]
    assert sum(r["in_window"] for r in replans) == 3 and len(replans) == 5
    assert all((r["mode"], r["devices"], r["chunks"]) ==
               ("sinkhorn+hier_at_scale+mesh_chunk", 4, 2) for r in replans)
    assert all(r["moved"] <= 0.01 * 8192 for r in replans)
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["after_window.resolve.moves_over_least"]["value"] == 0
    assert checks["after_window.resolve.rows_off_reference_loads"]["value"] == 0
    assert checks["after_window.quota_miss_max_seats"]["ok"]
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert {"resolve_ms", "solve_features_ms.mesh", "solve_exec_ms.mesh",
            "solve_apply_ms.mesh"} <= set(m)


@pytest.mark.parametrize("cell", [CELL, MESH_CELL])
def test_the_hold_readers_read_the_loops_own_clock_on_a_rehearsal(cell):
    """PR 37's metrics of the loop's holds and ticks are in the last line of
    a traced rehearsal of a one-chip cell and of the four-chip cell."""
    m = {k: v["value"] for k, v in _rehearsed(cell)[-1]["metrics"].items()}
    assert {"loop_hold_ms_per_s", "loop_hold_max_ms", "loop_hold_unnamed_ms_per_s",
            "tail_due_in_hold_share", "loop_turn_wait_ms"} <= set(m), sorted(m)
    assert 0.0 <= m["loop_hold_unnamed_ms_per_s"] <= m["loop_hold_ms_per_s"] < 1000.0
    assert m["loop_hold_max_ms"] >= 0.0 and 0.0 <= m["tail_due_in_hold_share"] <= 1.0
    # An idle loop's tick runs 0-1 ms late (the selector's rounding); a
    # rehearsal's loop is held now and then on top of that.
    assert 0.0 < m["loop_turn_wait_ms"] < 100.0


@pytest.mark.parametrize("objects, nodes, live, leave", [
    (32768, 64, 4, 1),
    (131072, 128, 8, 2),  # ~20 s: not slow enough to leave tier-1
])
def test_the_churn_at_size_on_the_cpu_with_the_monitors_on(tmp_path, objects, nodes, live, leave):
    """The cell at a size where the directory's own host work holds the
    loop the 8 monitors sample: the daemons' solves must keep moving what
    the events displace, not what a stepping derate re-plans. (PR 23's run
    at 131,072 x 128 passed only because no monitor stepped there.)"""
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "rio_tpu", tmp_path / "rio_tpu")
    os.symlink(REPO / "native", tmp_path / "native")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = json.loads((REPO / "benchmark/configs/presence-1m-1k-churn.json").read_text())
    conf.update(objects=objects, nodes=nodes, live_servers=live, down_at_start=3 * leave,
                placement_mode="sinkhorn", rehearsal={},  # the chip's route, named
                solve_mode={"cpu": "sinkhorn+collapsed"})
    (tmp_path / "benchmark/configs/presence-1m-1k-churn.json").write_text(json.dumps(conf))
    mix = json.loads((REPO / "benchmark/traffic/heartbeat-churn.json").read_text())
    mix["generators"][0]["rehearsal"] = {"rate_per_s": 200}
    mix["generators"][1]["rehearsal"] = {"leave": leave, "period_s": 3, "first_s": 1.5}
    (tmp_path / "benchmark/traffic/heartbeat-churn.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    lines = _cell(["--workload", CELL, "--seed", "2147483999", "--seconds", "12",
                   "--trace", "1"], cwd=tmp_path)
    last, checks = lines[-1], _served(lines)
    assert last["correct"] is True, [x for x in lines if x.get("ok") is False]
    assert last["failed"] == 0 and checks["churn.events_unserved"]["value"] == 0
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert 1.0 <= m["moved_per_displaced"] <= 1.25, m
    assert m["solves_discarded_per_event"] <= 1, m
    assert m["loop_lag_p99_ms"] is not None  # the monitors ran and were read
