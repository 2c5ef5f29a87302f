"""The churn cell's own checks: the reference against hand-worked cases,
the control, and two runs broken underneath that must come out
``correct: false`` (a row left on a leaver; a hand-off that skips the
source's deactivate). The rehearsal with and without trace is
``test_rehearsal.py``'s, which runs every cell of BENCHMARK.json."""

import json

import numpy as np
import pytest

from benchmark import run as runner
from benchmark.reference import churn

CELL = "presence-1m-1k-churn.heartbeat-churn"


def test_reference_a_leave_by_hand():
    # 12 rows even over 4 nodes; node 3 leaves: its 3 rows move, one to each.
    rec = churn.event(np.array([3, 3, 3, 3]), np.array([1.0, 1.0, 1.0, 0.0]), [3])
    assert rec["rows_on_leavers"] == 3
    assert rec["quotas"].tolist() == [4, 4, 4, 0]
    assert rec["least_moves"] == 3


def test_reference_a_leave_and_a_rejoin_by_hand():
    # 13 rows over 4 of 5 nodes (node 4 down), node 0 holds the odd row. Node
    # 0 leaves and node 4 rejoins in one flip: 13 over {1, 2, 3, 4} is 3 each
    # and one odd row that any of the four may hold. Node 0's 4 rows all move
    # (3 of them at least to node 4): 4 moves, not 4 + 3.
    before = np.array([4, 3, 3, 3, 0])
    rec = churn.event(before, np.array([0.0, 1.0, 1.0, 1.0, 1.0]), [0])
    assert rec["rows_on_leavers"] == 4
    assert rec["quotas"].sum() == 13 and rec["quotas"][0] == 0
    assert sorted(rec["quotas"][1:].tolist()) == [3, 3, 3, 4]
    assert rec["least_moves"] == 4
    least, most = churn.bounds(np.array([0.0, 1.0, 1.0, 1.0, 1.0]), 13)
    assert least.tolist() == [0, 3, 3, 3, 3] and most.tolist() == [0, 4, 4, 4, 4]
    # Replayed as a list of events, every plan the ideal one.
    out = churn.replay(before, np.array([1, 1, 1, 1, 0], bool),
                       [{"leavers": [0], "rejoiners": [4]}, {"leavers": [1], "rejoiners": [0]}])
    assert [r["least_moves"] for r in out] == [4, out[0]["counts"][1]]
    assert out[1]["counts"].sum() == 13 and out[1]["counts"][1] == 0
    assert out[1]["active"].tolist() == [True, False, True, True, True]
    # What two readings of the counts prove moved: what the shrinking nodes lost.
    assert churn.moved_at_least(before, out[0]["counts"]) == 4


def test_a_stop_of_the_process_is_witnessed_and_taken_out_of_an_event():
    import threading
    import time
    from types import SimpleNamespace

    from benchmark import harness

    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    gen = harness.plugin(bench, "traffic", "churn")
    audit = harness.plugin(bench, "audits", "churn_served")
    w = gen.Watcher(SimpleNamespace(_by_node={}), 4, poll_s=0.01)

    class Late(threading.Event):  # the third wake comes 3 s late: a stop
        n = 0

        def wait(self, timeout=None):
            self.n += 1
            if self.n == 3:
                late["from"] = time.perf_counter()
                real_sleep(gen.STOP_S + 0.3)
            return super().wait(timeout)

    late, real_sleep = {}, time.sleep
    w.stop_event = Late()
    w.start()
    real_sleep(gen.STOP_S + 0.6)
    w.stop_event.set()
    w.join(5)
    assert len(w.stops) == 1  # the 10 ms wakes before and after are no stops
    a, b = w.stops[0]
    assert a <= late["from"] + 0.05 and b - a >= gen.STOP_S + 0.3
    # An event of 9.5 s that holds a stop of 6 s ran 3.5 s; one that ended
    # before the stop, or a loop that was merely late, keeps its time.
    assert abs(audit.ran_between(10.0, 19.5, [(12.0, 18.0)]) - 3.5) < 1e-9
    assert abs(audit.ran_between(10.0, 11.5, [(12.0, 18.0)]) - 1.5) < 1e-9
    assert abs(audit.ran_between(10.0, 19.5, []) - 9.5) < 1e-9


def _run(capsys, *extra, seconds="6"):
    args = ["--workload", CELL, "--seed", "2147483659", "--seconds", seconds,
            "--rehearse-on-cpu", *extra]
    assert runner.main(args) == 0
    return [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]


def _failed(lines) -> set:
    return {x["check"] for x in lines if x.get("ok") is False}


@pytest.fixture
def restore_solves():
    """``lowprec.install`` wraps the solves where they live: put them back."""
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


def test_the_bfloat16_control_fails_the_quotas(capsys, restore_solves):
    lines = _run(capsys, "--control", "bfloat16", seconds="3")
    assert lines[-1]["correct"] is False and lines[-1]["control"] == "bfloat16"
    assert "after_setup.quota_miss_seats_exact" in _failed(lines)


def test_a_row_left_on_a_leaver_makes_the_run_incorrect(capsys, monkeypatch):
    from benchmark import harness
    from rio_tpu.migration import MigrationManager

    real = MigrationManager.apply_moves
    plans = {"n": 0}

    async def lossy(self, moves):
        # From the window's first plan on (set-up's warm event is the first
        # of all) every plan loses a move: a row stays where its node died.
        # (Lost once, the next event's solve would find and move it, inside
        # the limit: the daemons repair what a plan leaves behind.)
        plans["n"] += 1
        return await real(self, moves[1:] if plans["n"] >= 2 else moves)

    monkeypatch.setattr(MigrationManager, "apply_moves", lossy)
    monkeypatch.setattr(harness, "QUIESCE_LIMIT_S", 6.0)  # the wait, not a bound
    lines = _run(capsys)
    assert lines[-1]["correct"] is False
    summary = next(x["summary"] for x in lines if "summary" in x)
    assert any("seats on inactive nodes" in f for f in summary["failures"])


def _derate_one_live_server(monkeypatch):
    """From the first delta solve on, the directory prices the last live
    server at half: the next event's solve moves half of its rows away,
    through the source, live activations among them."""
    from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

    real = JaxObjectPlacement.sync_load

    def sync_load(self, view):
        real(self, view)
        if self._plan is not None and self._plan.delta_solves >= 1:
            self._nodes[self._node_order[-1]].reported_derate = 0.5

    monkeypatch.setattr(JaxObjectPlacement, "sync_load", sync_load)


def test_live_activations_handed_off_under_churn_stay_single(capsys, monkeypatch):
    _derate_one_live_server(monkeypatch)
    lines = _run(capsys)
    assert lines[-1]["correct"] is True, _failed(lines)
    assert lines[-1]["failed"] == 0


def test_a_handoff_that_skips_the_deactivate_makes_the_run_incorrect(capsys, monkeypatch):
    from rio_tpu.registry import Registry

    _derate_one_live_server(monkeypatch)

    async def skipped(self, type_name, object_id, app_data, before_remove=None):
        return False  # "no live activation": the instance stays in the registry

    monkeypatch.setattr(Registry, "deactivate", skipped)
    lines = _run(capsys)
    assert lines[-1]["correct"] is False
    assert "churn.activations_off_seat" in _failed(lines)


# ---------------------------------------------------------------------------
# The cell's per-layer readers: a number from records made by hand, None
# where the program keeps no such record (the parent commit)
# ---------------------------------------------------------------------------

NEW_METRICS = ("reseat_ms", "daemon_wait_ms", "solve_exec_ms.delta", "solve_apply_ms.delta",
               "handoff_ms.churn", "moved_per_displaced", "solves_discarded_per_event",
               "derate_steps_per_s", "class_refresh_roofline")


def _bench():
    from benchmark import harness

    return json.loads((harness.REPO / "BENCHMARK.json").read_text())


def _reader(name):
    from benchmark import harness

    return harness.plugin(_bench(), "layers", name)


def _run_record(log=None, trace=None):
    from types import SimpleNamespace

    return SimpleNamespace(
        bench=_bench(), window=(10.0, 58.0), spans=[], log=log or {}, trace=trace,
        config={"nodes": 1024}, cluster=SimpleNamespace(servers=[]),
    )


def test_the_new_metrics_are_declared_for_this_cell_alone_and_move_the_tail():
    declared = {m["name"]: m for m in _bench()["per_layer"]}
    for name in NEW_METRICS:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "request_p99_ms"
    e2e = {m["name"]: m for m in _bench()["end_to_end"]}
    assert CELL in e2e["request_p99_ms"]["workloads"]


def test_the_readers_read_nothing_where_the_program_records_nothing():
    from rio_tpu import tracing

    tracing.clear_stages()
    # No churn generator ran, no daemon stats, no trace:
    assert [_reader(n).read(_run_record()) for n in NEW_METRICS] == [None] * len(NEW_METRICS)
    # The parent commit under the generator: events, daemon stats, counters
    # without the new keys.
    ev = {"in_window": True, "t_flip": 12.0, "t_served": 13.5}
    log = {"churn": {"kind": "churn", "events": [ev], "gauges0": {}, "gauges1": {"x": 1.0}},
           "daemons0": {"rebalances_discarded": 2}, "daemons1": {"rebalances_discarded": 6}}
    got = {n: _reader(n).read(_run_record(log)) for n in NEW_METRICS}
    assert got["reseat_ms"] == 1500.0 and got["solves_discarded_per_event"] == 4.0
    assert {n for n, v in got.items() if v is not None} == {"reseat_ms", "solves_discarded_per_event"}


def test_the_readers_read_the_stage_log_and_the_counters():
    from rio_tpu import tracing

    tracing.clear_stages()
    ms = 1_000_000
    t = int(20e9)  # inside the window (10 s .. 58 s on perf_counter_ns)
    rows = [  # name, t0, t1, parent, call
        ("daemon.wait", t, t + 300 * ms, None, 0),
        ("solve.snapshot", t + 300 * ms, t + 304 * ms, "solve.full", 7),
        ("solve.delta", t + 305 * ms, t + 330 * ms, "solve.device", 7),
        ("solve.device", t + 304 * ms, t + 332 * ms, "solve.full", 7),
        ("solve.apply", t + 333 * ms, t + 341 * ms, "solve.full", 7),
        ("solve.device", t + 400 * ms, t + 900 * ms, "solve.full", 8),  # a full solve: not read
        ("migrate.apply_moves", t + 341 * ms, t + 491 * ms, "solve.full", 7),
        ("daemon.wait", int(5e9), int(6e9), None, 0),  # before the window: not read
    ]
    for name, t0, t1, parent, call in rows:
        tracing._log_stage(name, t0, t1, parent, call)
    ev = {"in_window": True, "t_flip": 19.5, "t_served": 20.6}
    log = {
        "churn": {"kind": "churn", "events": [ev, {**ev, "in_window": False}],
                  "gauges0": {"rio.load.derate_steps": 2.0},
                  "gauges1": {"rio.load.derate_steps": 5.0, "rio.place.delta.moved": 90.0}},
        "daemons0": {"moves": 10, "rebalances_discarded": 0},
        "daemons1": {"moves": 120, "rebalances_discarded": 1},
        "churn.least_moves": 100,
    }
    run = _run_record(log)
    got = {n: _reader(n).read(run) for n in NEW_METRICS}
    assert got["daemon_wait_ms"] == 300.0
    assert got["solve_exec_ms.delta"] == 28.0 and got["solve_apply_ms.delta"] == 12.0
    assert got["handoff_ms.churn"] == 150.0
    assert got["moved_per_displaced"] == 1.1 and got["solves_discarded_per_event"] == 1.0
    assert got["derate_steps_per_s"] == 3.0 / 48.0
    assert abs(got["reseat_ms"] - 1100.0) < 1e-6
    tracing.clear_stages()
    # The class refresh against its roofline: compute-bound by the count in costs/.
    trace = {"programs": {"_class_refresh_device": {"seconds": 35e-6 * 12, "calls": 12}},
             "device_kind": "TPU v5 lite"}
    traced = _run_record(log, trace)
    assert 0.5 < _reader("class_refresh_roofline").read(traced) < 2.0
    assert traced.log["roofline_bound"] == {"_class_refresh_device": "compute"}
