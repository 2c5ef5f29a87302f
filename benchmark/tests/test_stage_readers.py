"""The readers of the program's own records (stage log, loop-lag samples,
the saves' RED row): each reads a number on a rehearsal of its cell, reads
``None`` where the program keeps no such record, and the residue of a wave
is exact on a log made by hand."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
# The per-layer metrics PR 25 added and the cells it listed for each, by name:
# a later PR's entries (and the cells it appends to these) are its own to
# test, and adding them must leave this file as it is.
W, L = "presence-1m-1k.deploy-wave", "metric-aggregator-100k-8.loadall"
PINNED = {
    "wave_keys_ms": (W,), "wave_solve_wall_ms": (W,), "wave_resume_ms": (W,),
    "wave_apply_ms": (W,), "wave_lookup_ms": (W,), "wave_unattributed_ms": (W,),
    "wave_loop_held_ms": (W,), "loop_lag_p99_ms": (W,), "loop_late_ms_per_s.loadall": (L,),
    "loop_stall_max_ms": (W,), "gc_full_ms_per_s": (W,), "gc_full_ms_per_s.loadall": (L,),
    "state_save_p50_ms.loadall": (L,), "setup_place_s": (L, W), "setup_solve_s": (L, W),
}
_RESULTS: dict = {}


def _rehearsal(cell: str) -> dict:
    if cell not in _RESULTS:
        p = subprocess.run(
            [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload", cell,
             "--seed", "2147483693", "--seconds", "5", "--trace", "1", "--rehearse-on-cpu"],
            cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
            text=True, timeout=240,
        )
        assert p.returncode == 0, p.stderr[-2000:]
        _RESULTS[cell] = json.loads(p.stdout.strip().splitlines()[-1])
    return _RESULTS[cell]


def _reader(name: str):
    return harness.plugin(BENCH, "layers", name)


def _empty_run():
    return SimpleNamespace(
        bench=BENCH, window=(10.0, 58.0), spans=[], log={}, trace=None,
        cluster=SimpleNamespace(servers=[]), app=SimpleNamespace(HANDLER=("T", "m")),
    )


def test_every_metric_of_the_issue_is_declared_with_a_reader_file():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(PINNED) <= set(declared)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for name, listed in PINNED.items():
        m = declared[name]
        assert (REPO / "benchmark" / "layers" / f"{name}.py").is_file()
        assert m["source"] == "program_counter" and set(listed) <= set(m["workloads"])
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells)), name


@pytest.mark.parametrize(
    "cell,name", [(c, name) for name, listed in PINNED.items() for c in listed]
)
def test_a_new_reader_reads_a_number_on_a_rehearsal_of_its_cell(cell, name):
    metrics = _rehearsal(cell)["metrics"]
    assert name in metrics, sorted(metrics)
    value = metrics[name]["value"]
    assert isinstance(value, float) and value >= 0.0
    if name.startswith(("wave_", "setup_", "state_save")) and name != "wave_unattributed_ms":
        assert value > 0.0


@pytest.mark.parametrize("name", list(PINNED))
def test_a_new_reader_reads_nothing_where_the_program_keeps_no_record(name, monkeypatch):
    from rio_tpu import tracing

    run = _empty_run()
    tracing.clear_stages()
    assert _reader(name).read(run) is None  # an empty log
    # A commit from before the stage log (the driver lays these files over it).
    monkeypatch.delattr(tracing, "stage_log")
    run.cluster.servers = [SimpleNamespace(load_monitor=SimpleNamespace(stats=SimpleNamespace()))]
    assert _reader(name).read(run) is None


def test_the_residue_of_a_wave_is_exact_on_a_log_made_by_hand():
    st = _reader("_stages")
    ms = 1_000_000
    loop, worker = 1, 2
    waves = [(0, 100 * ms), (1000 * ms, 1200 * ms)]
    recs = [
        # wave 1: assign 0-60 with children 0-10, 10-40 (its own children 10-20, 25-40),
        # 45-60; a full collection 55-70 (10 ms beyond the assign); a lookup 80-95.
        ("place.keys", 0, 10 * ms, "place.assign", 7, loop),
        ("place.solve.build", 10 * ms, 20 * ms, "place.solve", 7, worker),
        ("place.solve.wait", 25 * ms, 40 * ms, "place.solve", 7, worker),
        ("place.solve", 10 * ms, 40 * ms, "place.assign", 7, worker),
        ("place.resume", 40 * ms, 45 * ms, "place.assign", 7, loop),
        ("place.apply", 45 * ms, 60 * ms, "place.assign", 7, loop),
        ("place.assign", 0, 60 * ms, None, 7, loop),
        ("gc.gen2", 55 * ms, 70 * ms, None, 0, loop),
        ("place.lookup", 80 * ms, 95 * ms, None, 8, worker),
        # wave 2: nothing but a stage that straddles its start (clipped to 1000-1010).
        ("place.lookup", 990 * ms, 1010 * ms, None, 9, worker),
        # outside every wave
        ("solve.full", 500 * ms, 600 * ms, None, 10, loop),
    ]
    # wave 1 bare: 20-25 (inside the solve, no child), 70-80, 95-100 = 20; wave 2: 190.
    assert st.unattributed_ms(waves, recs) == (20 + 190) / 2
    assert st.unattributed_ms(waves[:1], recs) == 20.0
    assert st.unattributed_ms([], recs) is None and st.unattributed_ms(waves, []) is None
    # Loop-held, wave 1: keys 10 + apply 15 + the assign's own 0 (its children,
    # the solve's 10-40 among them, cover 0-60); the wait place.resume is left out.
    assert st.loop_held_ms(waves[:1], recs) == 25.0
    assert st.union_ns([(0, 5), (3, 9), (20, 21)]) == 10
    assert {r[0] for r in st.leaves(recs)} == {
        "place.keys", "place.solve.build", "place.solve.wait", "place.resume",
        "place.apply", "gc.gen2", "place.lookup", "solve.full",
    }


def test_stall_reader_leaves_out_what_a_full_collection_covers(monkeypatch):
    from rio_tpu import tracing

    ms = 1_000_000
    tracing.clear_stages()
    # A tick due at 12.0 s ran at 12.5 s; a full collection covers 12.1-12.4 s of it.
    tracing._STAGE_LOG.append(("gc.gen2", 12_100 * ms, 12_400 * ms, None, 0, 1))
    stats = SimpleNamespace(lag_samples=[(12_500 * ms, 500.0), (13_500 * ms, 120.0),
                                         (5_000 * ms, 9_000.0),  # before the window
                                         (10_050 * ms, 700.0)])  # ran in it, due before it
    run = _empty_run()
    run.cluster.servers = [SimpleNamespace(load_monitor=SimpleNamespace(stats=stats))]
    try:
        assert _reader("loop_stall_max_ms").read(run) == pytest.approx(200.0)
        assert _reader("loop_lag_p99_ms").read(run) == 500.0
        assert _reader("loop_late_ms_per_s.loadall").read(run) == pytest.approx(320.0 / 48.0)
        assert _reader("gc_full_ms_per_s").read(run) == pytest.approx(300.0 / 48.0)
    finally:
        tracing.clear_stages()
