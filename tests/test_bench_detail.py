"""bench.py's sidecar and orchestration contract.

One sidecar file per platform (``BENCH_DETAIL.{tpu,cpu}.json``), each write
holding exactly what that run measured; a parent that never shares a
process with jax; a run that fails when any tier child fails.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402
from bench import _detail_platform, _write_detail  # noqa: E402


def _read(tmp, name):
    return json.loads((tmp / name).read_text())


def test_detail_platform_classification():
    assert _detail_platform({"solve_tier": {"platform": "tpu"}}) == "tpu"
    assert _detail_platform({"solve_tier": {"platform": "cpu"}}) == "cpu"
    assert _detail_platform({"sqlite_baseline_rate": 1}) == "cpu"
    # any tpu tier anywhere marks the run as hardware evidence
    assert (
        _detail_platform(
            {"solve_tier": {"platform": "cpu"}, "collapsed_tier": {"platform": "tpu"}}
        )
        == "tpu"
    )


def test_each_platform_writes_its_own_sidecar_only(tmp_path):
    _write_detail({"solve_tier": {"platform": "cpu", "run": 1}}, here=str(tmp_path))
    assert sorted(p.name for p in tmp_path.iterdir()) == ["BENCH_DETAIL.cpu.json"]
    _write_detail({"solve_tier": {"platform": "tpu", "run": 2}}, here=str(tmp_path))
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "BENCH_DETAIL.cpu.json",
        "BENCH_DETAIL.tpu.json",
    ]
    # A later host-only run cannot touch the hardware record, nor the
    # other way round.
    _write_detail({"solve_tier": {"platform": "cpu", "run": 3}}, here=str(tmp_path))
    assert _read(tmp_path, "BENCH_DETAIL.tpu.json")["solve_tier"]["run"] == 2
    assert _read(tmp_path, "BENCH_DETAIL.cpu.json")["solve_tier"]["run"] == 3


def test_a_write_holds_exactly_what_the_run_measured(tmp_path):
    """Nothing is read back or carried over from an earlier capture: a
    tier this run skipped or failed is absent (or None), not an older
    number under today's name."""
    _write_detail(
        {
            "collapsed_tier": {"platform": "tpu", "run": 1},
            "baseline_row5_hier": {"ok": True, "run": 1},
            "solve_tier": {"platform": "tpu", "run": 1},
        },
        here=str(tmp_path),
    )
    fresh = {"collapsed_tier": {"platform": "tpu", "run": 2}, "solve_tier": None}
    _write_detail(fresh, here=str(tmp_path))
    assert _read(tmp_path, "BENCH_DETAIL.tpu.json") == fresh


def test_corrupt_or_foreign_prior_sidecar_is_replaced(tmp_path):
    (tmp_path / "BENCH_DETAIL.tpu.json").write_text("{trunc")
    (tmp_path / "BENCH_DETAIL.cpu.json").write_text("[1, 2]")
    _write_detail({"solve_tier": {"platform": "tpu", "run": 1}}, here=str(tmp_path))
    _write_detail({"solve_tier": {"platform": "cpu", "run": 2}}, here=str(tmp_path))
    assert _read(tmp_path, "BENCH_DETAIL.tpu.json")["solve_tier"]["run"] == 1
    assert _read(tmp_path, "BENCH_DETAIL.cpu.json")["solve_tier"]["run"] == 2


# ---------------------------------------------------------------------------
# Who owns the chip: the parent orchestrates, children measure
# ---------------------------------------------------------------------------


def test_main_refuses_a_process_that_has_imported_jax():
    """One ownership model: device tiers are children, the parent stays
    off jax. This test process has jax imported (conftest), which is
    exactly the other model — main() must refuse it."""
    assert "jax" in sys.modules
    with pytest.raises(RuntimeError, match="must not share a process with jax"):
        bench.main()


def _run_main_with_fake_children(script: str) -> subprocess.CompletedProcess:
    """bench.main() in a fresh interpreter with _run_child replaced."""
    return subprocess.run(
        [sys.executable, "-c", script], cwd=str(REPO), capture_output=True,
        text=True, timeout=60,
    )


_FAKE_PRELUDE = """
import sys
import bench

COLLAPSED = {"ok": True, "platform": "tpu", "device": "fake", "n_obj": 8,
             "n_nodes": 2, "full_ms": 1.0, "single_shot_ms": 1.0, "rate": 8000.0,
             "dead_nodes": 1, "moved": 1, "displaced": 1}
HOST = {"ok": True, "platform": "cpu", "failed": [],
        "stages": {"sqlite_baseline_rate": 1000}}
bench._write_detail = lambda detail, here=None: None
"""


def test_main_exits_nonzero_when_a_tier_child_does():
    proc = _run_main_with_fake_children(
        _FAKE_PRELUDE
        + """
def fake(flags, deadline, env=None):
    if "--host-stages" in flags:
        return 0, HOST
    if "--collapsed" in flags:
        return 0, COLLAPSED
    if "--delta" in flags:
        return 98, None            # one tier child fails...
    return 0, {"ok": True, "platform": "tpu", "rate": 1.0}
bench._run_child = fake
rc = bench.main()
assert "jax" not in sys.modules      # the parent never imported it
sys.exit(rc)
"""
    )
    assert proc.returncode == 1, proc.stderr
    assert "FAILED: delta_tier(rc=98)" in proc.stderr
    # ...after the others have run: the headline is still printed.
    headline = json.loads(proc.stdout.strip().splitlines()[-1])
    assert headline["platform"] == "tpu" and headline["value"] == 8000.0


def test_main_prints_no_headline_and_fails_without_a_tpu():
    """No accelerator: every device tier exits EXIT_INIT_FAIL; there is no
    CPU fallback headline, and the run fails."""
    proc = _run_main_with_fake_children(
        _FAKE_PRELUDE
        + """
asked = []
def fake(flags, deadline, env=None):
    asked.append(flags)
    if "--host-stages" in flags:
        return 0, HOST
    return bench.EXIT_INIT_FAIL, None
bench._run_child = fake
rc = bench.main()
# One probe is enough: no further device tier was launched.
assert sum("--tier" in f for f in asked) == 1, asked
sys.exit(rc)
"""
    )
    assert proc.returncode == 1, proc.stderr
    assert proc.stdout.strip() == ""


def test_main_fails_when_a_host_stage_failed():
    proc = _run_main_with_fake_children(
        _FAKE_PRELUDE
        + """
def fake(flags, deadline, env=None):
    if "--host-stages" in flags:
        assert env["JAX_PLATFORMS"] == "cpu"
        return bench.EXIT_SOLVE_FAIL, {**HOST, "failed": ["qos"]}
    if "--collapsed" in flags:
        return 0, COLLAPSED
    return 0, {"ok": True, "platform": "tpu", "rate": 1.0}
bench._run_child = fake
sys.exit(bench.main())
"""
    )
    assert proc.returncode == 1, proc.stderr
    assert "FAILED: host:qos" in proc.stderr


def test_tier_children_inherit_the_platform_and_host_stage_flags_pin_cpu():
    flags = bench._tier_flags(1024, 30.0, "collapsed")
    assert flags[:4] == ["--tier", "1024", "--platform", "tpu"]
    assert flags[-1] == "--collapsed"
    # Every standalone host-stage flag resolves to a table row.
    assert {st[0] for st in bench._HOST_STAGES if st[0]} >= {
        "egress", "sharded", "migration", "series", "streams", "qos", "hier",
    }


def test_host_provenance_contract():
    """Every rpc_* stage stamps the host conditions it ran under; the
    sharded A/Bs are unreadable without cpu_count (1 core vs 4 inverts
    every conclusion)."""
    from bench import _host_provenance

    prov = _host_provenance()
    assert set(prov) == {"cpu_count", "sched_affinity", "loadavg"}
    assert isinstance(prov["cpu_count"], int) and prov["cpu_count"] >= 1
    if prov["sched_affinity"] is not None:
        assert prov["sched_affinity"] == sorted(prov["sched_affinity"])
        assert len(prov["sched_affinity"]) >= 1
    if prov["loadavg"] is not None:
        assert len(prov["loadavg"]) == 3


def test_rpc_sharded_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """rpc_sharded is a host stage: banked with its in-session baseline
    and host provenance, but never carried into a later tpu bank (its
    numbers are meaningless beside another session's baseline)."""
    stage = {
        "sqlite_baseline_in_session": 40000,
        "host": {"cpu_count": 1, "sched_affinity": [0], "loadavg": [0, 0, 0]},
        "one_worker": {"sharded_vs_plain": 0.97},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "rpc_sharded": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["rpc_sharded"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    assert "rpc_sharded" not in _read(tmp_path, "BENCH_DETAIL.tpu.json")


def test_rpc_egress_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The egress-coalescing A/B is a host stage: banked with its paired
    in-session numbers and host provenance, never carried into a later tpu
    bank (absolute host rates drift ±30-40% between sessions; only the
    paired off/on ratio under that run's box weather means anything)."""
    stage = {
        "asyncio": {
            "per_frame": [17000.0, 17100.0],
            "coalesced": [17900.0, 18000.0],
            "coalesced_vs_per_frame": 1.05,
        },
        "sqlite_baseline_in_session": 40000,
        "host": {"cpu_count": 1, "sched_affinity": [0], "loadavg": [0, 0, 0]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "rpc_egress": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["rpc_egress"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "rpc_egress" not in tpu and "rpc_egress_carried" not in tpu


def test_series_overhead_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The gauge time-series A/B is a host stage: banked beside its own
    session's host provenance, never carried into a later tpu bank (the
    paired off/on ratio only means something under that run's box weather)."""
    stage = {
        "msgs_per_sec": {"off": 18193.2, "on": 17942.5},
        "series_overhead_pct": 0.98,
        "samples_on": 263,
        "host": {"cpu_count": 4, "sched_affinity": [0, 1, 2, 3],
                 "loadavg": [0.5, 0.4, 0.3]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "series": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["series"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "series" not in tpu and "series_carried" not in tpu


def test_streams_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The durable-streams publish/deliver A/B is a host stage: banked
    beside its own session's host provenance, never carried into a later
    tpu bank (absolute host rates drift ±30-40% between sessions; only
    the paired backstop-off/on ratio under that run's box weather means
    anything)."""
    stage = {
        "publish_acks_per_sec": {"off": 1960.0, "on": 1978.0},
        "deliver_msgs_per_sec": {"off": 1903.0, "on": 1454.0},
        "redelivery_overhead_pct": 26.05,
        "delivered": {"off": 1248, "on": 1248},
        "host": {"cpu_count": 1, "sched_affinity": [0], "loadavg": [0, 0, 0]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "streams": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["streams"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "streams" not in tpu and "streams_carried" not in tpu


def test_affinity_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The affinity placement A/B is a host stage: banked beside its own
    session's host provenance, never carried into a later tpu bank (the
    bytes ratio and the paired sampler-off/on ratio only mean anything
    under that run's box weather)."""
    stage = {
        "tcp_bytes": {"blind": 1077981, "affinity": 93},
        "bytes_ratio": 11591.2,
        "pairs_colocated": 8,
        "sampler": {"sampler_overhead_pct": 0.66},
        "host": {"cpu_count": 4, "sched_affinity": [0, 1, 2, 3],
                 "loadavg": [0.5, 0.4, 0.3]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "affinity": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["affinity"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "affinity" not in tpu and "affinity_carried" not in tpu


def test_committed_cpu_capture_banks_affinity_with_provenance():
    """The repo's banked cpu sidecar carries the measured affinity A/B:
    the ISSUE 17 bars on disk — bytes-over-TCP dropped >= 2x after the
    edge-graph feedback, formerly cross-node delivery hops left the wire
    span rings, and the dispatch-path sampler priced under the paired
    off/on A/B — each stamped with the host conditions it ran under."""
    committed = Path(__file__).resolve().parent.parent / "BENCH_DETAIL.cpu.json"
    aff = json.loads(committed.read_text())["affinity"]
    assert aff["bytes_ratio"] >= 2.0
    assert aff["tcp_bytes"]["affinity"] < aff["tcp_bytes"]["blind"]
    assert aff["delivery_wire_spans"]["blind"] > 0
    assert aff["delivery_wire_spans"]["affinity"] == 0
    assert aff["pairs_colocated"] == aff["partitions"]
    assert "+affinity" in aff["solved_as"]
    assert aff["sampler"]["sampled_on"] > 0
    assert set(aff["host"]) == {"cpu_count", "sched_affinity", "loadavg"}


def test_committed_cpu_capture_banks_streams_with_provenance():
    """The repo's banked cpu sidecar carries the measured streams A/B:
    both modes delivered every acked publish (zero loss on disk), and
    the stage is stamped with the host conditions it ran under."""
    committed = Path(__file__).resolve().parent.parent / "BENCH_DETAIL.cpu.json"
    streams = json.loads(committed.read_text())["streams"]
    assert set(streams["publish_acks_per_sec"]) == {"off", "on"}
    assert set(streams["deliver_msgs_per_sec"]) == {"off", "on"}
    assert streams["delivered"]["off"] == streams["delivered"]["on"] > 0
    assert set(streams["host"]) == {"cpu_count", "sched_affinity", "loadavg"}


def test_committed_cpu_capture_banks_series_with_provenance():
    """The repo's banked cpu sidecar carries the measured series A/B — the
    ISSUE's ≤1% bar is evidence on disk, stamped with host conditions."""
    committed = Path(__file__).resolve().parent.parent / "BENCH_DETAIL.cpu.json"
    series = json.loads(committed.read_text())["series"]
    assert series["series_overhead_pct"] <= 1.0
    assert series["samples_on"] > 0
    assert set(series["host"]) == {"cpu_count", "sched_affinity", "loadavg"}
    assert set(series["msgs_per_sec"]) == {"off", "on"}


def test_spans_overhead_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The span-retention A/B is a host stage: banked beside its own
    session's host provenance, never carried into a later tpu bank."""
    stage = {
        "msgs_per_sec": {"off": 17976.9, "on": 17785.0},
        "spans_overhead_pct": 1.36,
        "retained_on": 792,
        "tail_captured_on": 792,
        "slo_ms": 1.0,
        "host": {"cpu_count": 1, "sched_affinity": [0],
                 "loadavg": [0.5, 0.4, 0.3]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "spans": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["spans"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "spans" not in tpu and "spans_carried" not in tpu


def test_committed_cpu_capture_banks_spans_with_provenance():
    """The repo's banked cpu sidecar carries the measured waterfall A/B —
    the ISSUE's ≤2% bar is evidence on disk, priced with tail capture
    ARMED (tail_captured_on > 0: retention writes actually happened),
    stamped with host conditions."""
    committed = Path(__file__).resolve().parent.parent / "BENCH_DETAIL.cpu.json"
    spans = json.loads(committed.read_text())["spans"]
    assert spans["spans_overhead_pct"] <= 2.0
    assert spans["tail_captured_on"] > 0
    assert spans["retained_on"] >= spans["tail_captured_on"]
    assert spans["slo_ms"] <= 250.0  # priced at/below the shipping default
    assert set(spans["host"]) == {"cpu_count", "sched_affinity", "loadavg"}
    assert set(spans["msgs_per_sec"]) == {"off", "on"}


def test_hier_mesh_ab_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The mesh x chunk vs chunked-only paired A/B is a host stage: banked
    beside its own session's host provenance, never carried into a later
    tpu bank (absolute host solve times drift between sessions; only the
    paired in-session ratio means anything)."""
    stage = {
        "n_obj": 2_097_152,
        "devices": 8,
        "cell_rows": 65_536,
        "mesh_chunk": {"first_chunk_ms": 8937.0, "wall_s": 24.5},
        "chunked_only": {"first_chunk_ms": 9627.0, "wall_s": 25.4},
        "transport_cost": {"ratio": 1.01},
        "host": {"cpu_count": 1, "sched_affinity": [0], "loadavg": [0, 0, 0]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "hier_mesh_ab": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["hier_mesh_ab"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "hier_mesh_ab" not in tpu and "hier_mesh_ab_carried" not in tpu


def test_committed_cpu_capture_banks_hier_mesh_ab_with_provenance():
    """The repo's banked cpu sidecar carries the ISSUE 18 paired A/B:
    mesh x chunk vs chunked-only at MATCHED N on the 8-virtual-device
    mesh, quality parity on disk (transport-cost ratio <= 1.05), both
    arms' chunk timings present (first chunk carries the compile), and
    the stage stamped with the host conditions it ran under."""
    committed = Path(__file__).resolve().parent.parent / "BENCH_DETAIL.cpu.json"
    ab = json.loads(committed.read_text())["hier_mesh_ab"]
    assert ab["devices"] == 8
    assert ab["n_obj"] == ab["mesh_chunk"]["n_chunks"] * 8 * ab["cell_rows"]
    assert ab["transport_cost"]["ratio"] <= 1.05
    for arm in ("mesh_chunk", "chunked_only"):
        assert ab[arm]["overflow"] == 0
        assert len(ab[arm]["chunk_ms"]) == ab[arm]["n_chunks"] > 1
        assert ab[arm]["first_chunk_ms"] >= max(ab[arm]["chunk_ms"][1:])
    assert set(ab["host"]) == {"cpu_count", "sched_affinity", "loadavg"}


def test_autoscale_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The autoscale stage (idle-overhead A/B + ramp soak) is a host
    stage: banked under host provenance — a banked ramp IS a passed soak —
    and never carried into a later tpu bank (the paired off/on ratio and
    the soak's latencies only mean anything under that run's box weather)."""
    stage = {
        "idle": {
            "msgs_per_sec": {"off": 18000.0, "on": 17900.0},
            "autoscale_overhead_pct": 0.55,
            "controller_ticks_on": 48,
        },
        "ramp": {
            "scale_outs": 1,
            "scale_ins": 1,
            "lost": 0,
            "killed_mid_drain": "127.0.0.1:39525",
            "p99_ms": 36.1,
        },
        "host": {"cpu_count": 4, "sched_affinity": [0, 1, 2, 3],
                 "loadavg": [0.5, 0.4, 0.3]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "autoscale": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["autoscale"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "autoscale" not in tpu and "autoscale_carried" not in tpu


def test_qos_banks_to_cpu_sidecar_and_never_carries(tmp_path):
    """The QoS uniform-overhead + flood-protection A/B is a host stage:
    banked beside its own session's host provenance, never carried into a
    later tpu bank (absolute rates and latencies drift with box weather;
    only the paired off/on ratios under that run's conditions mean
    anything)."""
    stage = {
        "uniform": {
            "msgs_per_sec": {"off": 11509.3, "on": 13790.3},
            "qos_overhead_pct": -1.11,
            "admitted_on": 25477,
        },
        "flood": {
            "off": {"interactive_p99_ms": 79.7},
            "on": {"interactive_p99_ms": 17.9},
            "interactive_p99_improvement": 4.45,
            "interactive_sheds_on": 0,
        },
        "host": {"cpu_count": 1, "sched_affinity": [0], "loadavg": [0, 0, 0]},
    }
    _write_detail(
        {"solve_tier": {"platform": "cpu"}, "qos": stage},
        here=str(tmp_path),
    )
    banked = _read(tmp_path, "BENCH_DETAIL.cpu.json")
    assert banked["qos"] == stage
    # A later tpu run must not inherit it.
    _write_detail({"solve_tier": {"platform": "tpu"}}, here=str(tmp_path))
    tpu = _read(tmp_path, "BENCH_DETAIL.tpu.json")
    assert "qos" not in tpu and "qos_carried" not in tpu


def test_committed_cpu_capture_banks_qos_with_provenance():
    """The repo's banked cpu sidecar carries the measured QoS A/B: both
    ISSUE 20 bars are evidence on disk — uniform unclassified traffic
    pays <= ~2% for the scheduler, the interactive tenant's p99 under a
    bulk flood is >= 3x better with QoS on, and the flood never caused a
    single interactive shed — stamped with the host conditions."""
    committed = Path(__file__).resolve().parent.parent / "BENCH_DETAIL.cpu.json"
    qos = json.loads(committed.read_text())["qos"]
    assert qos["uniform"]["qos_overhead_pct"] <= 2.0
    assert qos["uniform"]["admitted_on"] > 0
    assert qos["flood"]["interactive_p99_improvement"] >= 3.0
    assert qos["flood"]["interactive_sheds_on"] == 0
    assert set(qos["host"]) == {"cpu_count", "sched_affinity", "loadavg"}
