"""The readers of the loop's own clock (PR 37: ``layers/_holds.py`` and the
six metrics over it): each is exact on a hold log, a tick log and a request
log made by hand, reads ``None`` where the program keeps no such log, where
no tick ran over the window and where a full ring no longer reaches it."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from rio_tpu import tracing  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
W, C = "presence-1m-1k.deploy-wave", "presence-1m-1k-churn.heartbeat-churn"
R, L = "presence-4m-1k-mesh.resolve", "metric-aggregator-100k-8.loadall"
# name -> (cells, moves, source, unit, better)
DECLARED = {
    "loop_hold_ms_per_s": ((W, C, R), "request_p99_ms", "program_span", "ms/s", "lower"),
    "loop_hold_max_ms": ((W, C, R), "request_p99_ms", "program_span", "ms", "lower"),
    "loop_hold_unnamed_ms_per_s": ((W, C, R), "request_p99_ms", "program_span", "ms/s", "lower"),
    "tail_due_in_hold_share": (
        (W, C, R), "request_p99_ms", "program_span", "requests/request", "higher"),
    "loop_turn_wait_ms": ((W, C, R), "request_p99_ms", "program_counter", "ms", "lower"),
    "loop_turn_wait_ms.loadall": ((L,), "requests_per_s", "program_counter", "ms", "lower"),
}
MS = 1_000_000
LOOP = 7  # the loop's thread id in the logs made by hand


def _reader(name: str):
    return harness.plugin(BENCH, "layers", name)


def _run(log=None, spans=()):
    """A run whose window is 10 s .. 58 s on the stage log's clock."""
    return SimpleNamespace(
        bench=BENCH, window=(10.0, 58.0), spans=list(spans), log=log or {}, trace=None,
        cluster=SimpleNamespace(servers=[]), app=SimpleNamespace(HANDLER=("T", "m")),
    )


@pytest.fixture(autouse=True)
def by_hand():
    """Every test writes the rings itself, and leaves them empty."""
    tracing.clear_stages()
    yield
    tracing.clear_stages()


def _put(holds=(), ticks=(), stages=()):
    """As the tick logs holds (no name yet) and the program its stages (none
    of these a wait)."""
    tracing._HOLD_NEW.extend((a, b, LOOP) for a, b in holds)
    tracing._TICK_LOG.extend(ticks)
    tracing._STAGE_LOG.extend((*rec, False) for rec in stages)


# Roll-up rows that bracket the window 10 s .. 58 s: 9.5 s and 58.5 s.
BRACKET = [
    (9_000 * MS, 1_000, 400 * MS, LOOP), (9_500 * MS, 1_100, 500 * MS, LOOP),
    (30_000 * MS, 5_000, 2_000 * MS, LOOP),
    (58_500 * MS, 10_900, 20_100 * MS, LOOP), (59_500 * MS, 11_100, 20_200 * MS, LOOP),
]


def test_every_metric_of_the_issue_is_declared_with_a_reader_file():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert list(declared)[-len(DECLARED):] == list(DECLARED)  # appended, in the issue's order
    for name, (listed, moves, source, unit, better) in DECLARED.items():
        m = declared[name]
        assert (REPO / "benchmark" / "layers" / f"{name}.py").is_file()
        assert m == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": "event loop and interpreter", "moves": moves,
                     "workloads": list(listed)}
        assert set(listed) <= set(e2e[moves].get("workloads", cells)), name


@pytest.mark.parametrize("name", list(DECLARED))
def test_a_hold_reader_reads_nothing_where_there_is_nothing_to_read(name, monkeypatch):
    log = {"heartbeats": {"kind": "open_loop", "due": np.array([20.0]), "done": np.array([20.1]),
                          "ok": np.array([True]), "timeout_s": 5.0}}
    # Empty rings: no tick ran over the window.
    assert _reader(name).read(_run(log)) is None
    # Rows on one side of the window only.
    _put(holds=[(20_000 * MS, 20_100 * MS)], ticks=BRACKET[:3])
    assert _reader(name).read(_run(log)) is None
    # A full ring whose oldest record is younger than the window's start:
    # never a short sum.
    tracing.clear_stages()
    _put(ticks=BRACKET)
    tracing._HOLD_NEW.extend(
        (30_000 * MS + i, 30_000 * MS + i + 1, LOOP) for i in range(tracing.HOLD_LOG_SIZE))
    assert _reader(name).read(_run(log)) is None  # (the turn wait takes the holds out too)
    tracing.clear_stages()
    tracing._TICK_LOG.extend(
        (11_000 * MS + i * MS, i, i, LOOP) for i in range(tracing.TICK_LOG_SIZE))
    assert _reader(name).read(_run(log)) is None
    # A commit from before the loop kept its own account (the driver lays
    # these files over it).
    tracing.clear_stages()
    _put(holds=[(20_000 * MS, 20_100 * MS)], ticks=BRACKET)
    assert _reader(name).read(_run(log)) is not None
    monkeypatch.delattr(tracing, "hold_log")
    monkeypatch.delattr(tracing, "tick_log")
    assert _reader(name).read(_run(log)) is None


def test_the_hold_sums_are_exact_on_a_log_made_by_hand():
    stages = [
        # The second hold's stage covers 60 of its 100 ms; the third's all.
        ("solve.snapshot", 20_000 * MS, 20_060 * MS, "solve.full", 3, LOOP),
        ("solve.full", 20_000 * MS, 20_200 * MS, None, 3, LOOP),
        ("place.apply", 57_900 * MS, 58_300 * MS, "place.assign", 4, LOOP),
        ("place.assign", 57_900 * MS, 58_300 * MS, None, 4, LOOP),
    ]
    _put(
        holds=[
            (9_950 * MS, 10_030 * MS),   # straddles the window's start: 30 ms inside, unnamed
            (20_000 * MS, 20_100 * MS),  # solve.snapshot, 40 ms of it bare
            (40_000 * MS, 40_050 * MS),  # unnamed
            (57_900 * MS, 58_300 * MS),  # place.apply, straddles the end: 100 ms inside
            (58_400 * MS, 58_900 * MS),  # after the window
            (5_000 * MS, 5_500 * MS),    # before it
        ],
        ticks=BRACKET, stages=stages,
    )
    run = _run()
    assert [h[2] for h in sorted(tracing.hold_log())] == [
        "unnamed", "unnamed", "solve.snapshot", "unnamed", "place.apply", "unnamed"]
    assert _reader("loop_hold_ms_per_s").read(run) == pytest.approx((30 + 100 + 50 + 100) / 48)
    # Began in the window: 100, 50, and the last as far as it lies in it (100).
    assert _reader("loop_hold_max_ms").read(run) == 100.0
    # All of the unnamed ones (30 + 50) and the snapshot's bare 40.
    assert _reader("loop_hold_unnamed_ms_per_s").read(run) == pytest.approx((30 + 40 + 50) / 48)
    # No hold began in the window: the longest is 0, not None.
    tracing.clear_stages()
    _put(holds=[(9_950 * MS, 10_030 * MS)], ticks=BRACKET)
    assert _reader("loop_hold_max_ms").read(run) == 0.0
    assert _reader("loop_hold_ms_per_s").read(run) == pytest.approx(30 / 48)
    tracing.clear_stages()
    _put(ticks=BRACKET)
    assert _reader("loop_hold_ms_per_s").read(run) == 0.0  # the tick ran; nothing held


def test_the_turn_wait_counts_the_window_alone():
    """The harness stops the profiler on the servers' loop right after the
    window (13.5 s in the four-chip cell) and collects right before it: both
    fall between the roll-up rows that bracket the window, and neither is the
    window's."""
    rows = [
        (9_000 * MS, 1_000, 1_000 * MS, LOOP),
        (11_000 * MS, 1_301, 1_800 * MS, LOOP),    # + a hold of 500 and 300 ticks 1 ms late
        (57_000 * MS, 10_302, 10_900 * MS, LOOP),  # + a hold of 100 and 9,000 ticks 1 ms late
        (72_000 * MS, 10_603, 24_550 * MS, LOOP),  # + a hold of 13,500 and 300 ticks 0.5 ms late
        (73_000 * MS, 10_803, 24_560 * MS, LOOP),
    ]
    _put(
        holds=[
            (9_200 * MS, 9_700 * MS),    # set-up's last work: none of it in the window
            (20_000 * MS, 20_100 * MS),  # the window's own
            (57_990 * MS, 71_490 * MS),  # due in the window's last 10 ms, ran 13.5 s later
        ],
        ticks=rows,
    )
    # Of the first stretch the loop turned 1,500 ms, 1,000 of them in the
    # window: 200 of its 300 short ticks and of their 300 ms. Of the last it
    # turned 1,500 ms, 990 in the window: 198 of 300 ticks, 99 of 150 ms. The
    # holds: 100 ms and 10 ms inside, two ticks due inside.
    late, ticks = 200 + 9_000 + 99 + 100 + 10, 200 + 9_000 + 198 + 2
    for name in ("loop_turn_wait_ms", "loop_turn_wait_ms.loadall"):
        assert _reader(name).read(_run()) == pytest.approx(late / ticks)
    # (The rows' difference alone would read 2.45 ms.)
    assert (24_550 - 1_000) / (10_603 - 1_000) == pytest.approx(2.452, abs=1e-3)
    # No hold at all: each edge stretch gives the share of its time that is
    # the window's.
    tracing.clear_stages()
    _put(ticks=BRACKET)
    late = 1_500 * 20 / 20.5 + 18_100 * 28 / 28.5
    ticks = 3_900 * 20 / 20.5 + 5_900 * 28 / 28.5
    assert _reader("loop_turn_wait_ms").read(_run()) == pytest.approx(late / ticks)


def test_the_tail_share_is_exact_on_a_request_log_made_by_hand():
    _put(holds=[(20_000 * MS, 20_100 * MS), (40_000 * MS, 40_050 * MS)], ticks=BRACKET)
    n = 400  # the slowest 1 % are 4 requests
    due = np.full(n, 30.0)
    took = np.full(n, 0.002)
    ok = np.ones(n, bool)
    # Due on a hold's first and on its last nanosecond: inside. One
    # nanosecond after: outside. A failure is slowest of all, wherever due.
    due[:5] = [20.0, 20.1, 20.100000001, 40.02, 50.0]
    took[:5] = [0.30, 0.29, 0.28, 0.001, 0.001]
    ok[4] = False
    log = {
        "heartbeats": {"kind": "open_loop", "due": due, "done": due + took, "ok": ok,
                       "timeout_s": 5.0},
        "drivers": {"kind": "closed_loop", "due": np.array([20.05]), "done": np.array([29.0]),
                    "ok": np.array([True]), "timeout_s": 5.0},  # not open loop: left out
    }
    holds = _reader("_holds")
    run = _run(log)
    slow = holds.slowest(run)
    assert sorted(slow.tolist()) == [20_000 * MS, 20_100 * MS, 20_100 * MS + 1, 50_000 * MS]
    assert _reader("tail_due_in_hold_share").read(run) == 0.5
    # No open-loop requests (loadall): nothing to read.
    assert _reader("tail_due_in_hold_share").read(_run({"drivers": log["drivers"]})) is None


def test_the_table_names_the_harness_span_of_a_hold_the_program_left_unnamed(capsys):
    _put(
        holds=[(20_000 * MS, 20_100 * MS), (40_000 * MS, 40_050 * MS), (41_000 * MS, 41_020 * MS),
               (41_022 * MS, 41_060 * MS)],  # the very next tick late again: no cause, a label
        ticks=BRACKET,
        stages=[("solve.snapshot", 19_990 * MS, 20_095 * MS, "solve.full", 3, LOOP),
                ("solve.full", 19_990 * MS, 20_500 * MS, None, 3, LOOP)],
    )
    spans = [("bench.wave", 39_900 * MS, 40_400 * MS),
             ("bench.wave.lookup_batch", 39_990 * MS, 40_100 * MS)]
    holds = _reader("_holds")
    inner = holds.bench_span(_run(spans=spans), 40_000 * MS, 40_050 * MS)
    assert inner == "in bench.wave.lookup_batch"
    assert holds.bench_span(_run(spans=spans), 41_000 * MS, 41_020 * MS) is None
    # What a generator did right before its span (a wave's ids, in a thread).
    assert holds.bench_span(_run(spans=spans), 39_860 * MS, 39_895 * MS) == "before bench.wave"
    _reader("loop_hold_ms_per_s").read(_run(spans=spans))
    table = capsys.readouterr().err
    assert "unnamed in bench.wave.lookup_batch" in table
    after = next(line.split() for line in table.splitlines() if " unnamed after a hold " in line)
    assert after[-5:] == ["1", "38.00", "38.00", "38.00", "0"]
    row = next(line.split() for line in table.splitlines() if " solve.snapshot " in line)
    assert row[-5:] == ["1", "100.00", "100.00", "5.00", "0"]
    # 208 ms of holds in 48 s: the snapshot's 5 left over, 50 in a span, 38
    # right after a hold, 20 bare: all of it counted as unnamed.
    assert "unnamed 2.35 ms/s = in the harness's spans 1.04 + right before one 0.00 + the next " \
           "tick after a hold 0.79 + left over by the stages of named holds 0.10 + bare 0.42" in table
    assert _reader("loop_hold_unnamed_ms_per_s").read(_run(spans=spans)) == pytest.approx(113 / 48)
    assert "longest hold 100.00 ms at 10.000 s of the window, solve.snapshot: " \
           "its stage record is 105.00 ms, 95.00 of them inside" in table
