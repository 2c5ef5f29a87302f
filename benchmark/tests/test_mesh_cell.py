"""The four-chip cell (PR 34) at sizes a test holds: rehearsed on the chip's
route, its readers, its cost function, and the control that lowers the
two-level solve.

The rehearsal binds its members at the churn cell's loopback addresses
(``127.77.x.y:7000``): never at the same time as ``test_churn.py``'s runs.
"""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness, lowprec  # noqa: E402
from benchmark.reference import two_level  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "presence-4m-1k-mesh.resolve"
# What makes 8,192 rows take the chip's route: 4 devices x 2 chunks of 1,024.
ROUTE_ENV = {
    "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    "RIO_TPU_FLAT_REBALANCE_MAX_ROWS": "1024", "RIO_TPU_HIER_CHUNK_ROWS": "1024",
}
NEW = ("resolve_ms", "solve_features_ms.mesh", "solve_exec_ms.mesh", "solve_apply_ms.mesh",
       "mesh_device_ms.resolve", "mesh_cell_roofline")


def _rehearse(trace: int) -> list:
    p = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload", CELL, "--seed",
         "2147483997", "--seconds", "8", "--trace", str(trace), "--rehearse-on-cpu"],
        cwd=REPO, env={**os.environ, **ROUTE_ENV}, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(x) for x in p.stdout.strip().splitlines()]


def test_the_cell_is_declared_as_the_issue_names_it():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("presence-4m-1k-mesh", "resolve", 4)
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == ["live_servers", "objects"] and len(conf["source"]) <= 200
    assert "src/services.rs:25-55" in conf["source"] and "config 5" in conf["source"]
    file = json.loads((REPO / conf["file"]).read_text())
    assert (file["objects"], file["nodes"], file["chips"]) == (4_194_304, 1024, 4)
    assert file["solve_mode"]["tpu"] == "sinkhorn+hier_at_scale+mesh_chunk"
    assert set(conf["reduced"]) == set(file["reduced"])
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "request_p99_ms"
        assert (REPO / "benchmark" / "layers" / f"{name}.py").is_file()
    assert declared["mesh_cell_roofline"]["unit"] == "%"
    shared = ("generator_late_p99_ms", "server_p50_ms.heartbeat", "loop_lag_p99_ms",
              "loop_stall_max_ms", "gc_full_ms_per_s", "setup_place_s", "setup_solve_s")
    assert all(declared[name]["workloads"][-1] == CELL for name in shared)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_chips_route(trace):
    lines = _rehearse(trace)
    last = lines[-1]
    assert last["correct"] is True, [x for x in lines if x.get("ok") is False]
    assert last["attempted"] > 0 and last["failed"] == 0
    replans = [x["replan"] for x in lines if "replan" in x]
    # Set-up's warm one, the window's three (1, 4, 7 s), the audit's.
    assert [r["in_window"] for r in replans] == [False, True, True, True, False]
    for r in replans:
        assert (r["mode"], r["devices"], r["chunks"]) == ("sinkhorn+hier_at_scale+mesh_chunk", 4, 2)
        assert r["moved"] <= 0.01 * 8192
    checks = {x["check"]: x for x in lines if "check" in x}
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["after_window.quota_miss_max_seats"]["limit"] == 15
    assert checks["after_window.resolve.moves_over_least"]["value"] == 0
    assert checks["after_window.resolve.rows_off_reference_loads"]["value"] == 0
    assert checks["after_window.resolve.coarse_potentials_max_diff"]["value"] < 2e-3
    m = {k: v["value"] for k, v in last["metrics"].items()}
    if trace:
        # The CPU backend's trace names no programs, so the two readers of
        # the device trace find nothing there and say nothing.
        assert {"resolve_ms", "solve_features_ms.mesh", "solve_exec_ms.mesh",
                "solve_apply_ms.mesh", "setup_solve_s", "loop_lag_p99_ms"} <= set(m)
        assert m["solve_features_ms.mesh"] < m["solve_exec_ms.mesh"] <= m["resolve_ms"]
    else:
        assert set(m) == {"setup_s", "request_p99_ms"}


def _run_with(trace=None, replans=2):
    recs = [{"t_call": 1.0 + k, "t_commit": 1.5 + k, "devices": 4, "chunks": 2}
            for k in range(replans)]
    return SimpleNamespace(
        bench=BENCH, window=(10.0, 58.0), spans=[], trace=trace,
        log={"resolve": {"kind": "full_resolve", "replans": recs}},
        config={"nodes": 1024, "solver": {"features": 16, "group_size": 8, "iters": 30}},
        cluster=SimpleNamespace(servers=[], names=np.zeros(4_194_304, object)),
    )


def test_the_device_readers_read_the_cell_program_and_say_nothing_without_it():
    layer = lambda name: harness.plugin(BENCH, "layers", name)  # noqa: E731
    for name in ("mesh_device_ms.resolve", "mesh_cell_roofline"):
        assert layer(name).read(_run_with()) is None
        other = {"programs": {"_class_refresh_device": {"seconds": 1.0, "calls": 3}},
                 "devices": 1, "device_kind": "TPU v5 lite"}
        assert layer(name).read(_run_with(other)) is None  # the parent's trace
    # Two re-plans of two chunk steps on four devices, 0.25 s a cell.
    trace = {"programs": {"mesh_cell_solve": {"seconds": 4.0, "calls": 16}}, "devices": 4,
             "device_kind": "TPU v5 lite"}
    run = _run_with(trace)
    assert layer("mesh_device_ms.resolve").read(run) == pytest.approx(500.0)
    share = layer("mesh_cell_roofline").read(run)
    need = harness.plugin(BENCH, "costs", "mesh_cell_solve").cost(
        rows=524_288, feat=16, nodes=1024, group_size=8, iters=30)
    assert share == pytest.approx(100 * max(need["flops"] / 197e12, need["bytes"] / 819e9) / 0.25)
    assert 0 < share < 100 and run.log["roofline_bound"]["mesh_cell_solve"] == "compute"
    assert layer("resolve_ms").read(run) == pytest.approx(500.0)
    assert layer("resolve_ms").read(_run_with(replans=0)) is None


def test_the_cells_cost_counts_one_cell_from_its_shapes():
    cost = harness.plugin(BENCH, "costs", "mesh_cell_solve").cost
    one = cost(rows=524_288, feat=16, nodes=1024, group_size=8, iters=30)
    # The affinities dominate: rows x nodes x features multiply-adds.
    assert 2 * 524_288 * 1024 * 16 < one["flops"] < 2 * 2 * 524_288 * 1024 * 16
    assert one["bytes"] == 4 * (524_288 * 18 + 16 * 1024 + 4 * 1024 + 2 * 128)
    assert cost(rows=1_048_576, feat=16, nodes=1024, group_size=8, iters=30)["flops"] > 2 * one["flops"]


@pytest.fixture
def restore_solves():
    from importlib import import_module

    mods = [import_module(m) for m in (
        "rio_tpu.ops", "rio_tpu.object_placement.jax_placement", "rio_tpu.ops.assignment",
        "rio_tpu.ops.scaling", "rio_tpu.ops.sinkhorn", "rio_tpu.ops.structured",
    )]
    saved = [dict(vars(m)) for m in mods]
    yield
    for m, d in zip(mods, saved):
        for k, v in d.items():
            if getattr(m, k, None) is not v:
                setattr(m, k, v)


def test_bfloat16_reaches_the_two_level_route_and_misses_the_references_loads(restore_solves):
    """One cell at shares bfloat16 cannot hold (1,260.3 rows a node: its
    spacing there is 8). ``lowprec`` patches the ``ops`` modules only; the
    two-level body reaches its solve steps through them when it is traced."""
    import jax.numpy as jnp

    from rio_tpu.parallel.hierarchical import hierarchical_assign

    rng = np.random.default_rng(7)
    rows, nodes = 16_384, 16
    cap = np.ones(nodes, np.float32)
    cap[[2, 9, 12]] = 0.0
    feat = rng.standard_normal((rows, 16)).astype(np.float32)
    node_feat = rng.standard_normal((16, nodes)).astype(np.float32)
    lo, hi = two_level.load_bounds(cap, rows, 1)

    def off(iters):  # a static argument of its own: a trace of its own
        res = hierarchical_assign(
            feat, jnp.asarray(node_feat), jnp.asarray(cap), jnp.asarray((cap > 0) * 1.0),
            n_groups=2, bucket=16_384, coarse_iters=iters, fine_iters=30,
        )
        loads = np.bincount(np.asarray(res.assignment), minlength=nodes)
        return two_level.rows_off_bounds(loads, lo, hi)

    assert off(30) == 0
    wrapped = lowprec.install("bfloat16")
    assert "rio_tpu.ops.scaling.scaling_sinkhorn" in wrapped
    assert off(29) > 16
