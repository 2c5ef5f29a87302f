"""Every cell end to end on the CPU at tiny sizes, the refusals without a
chip, and a cell, a mix and a metric added as files and found by name."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run(args, cwd=REPO, script=None, env=ENV, timeout=240):
    cmd = [sys.executable, str(script or REPO / "benchmark" / "run.py"), *args]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end(cell, trace):
    p = _run(["--workload", cell, "--seed", "2147483659", "--seconds", "5",
              "--trace", str(trace), "--rehearse-on-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert all(x.get("rehearsal") is True and x.get("platform") == "cpu" for x in lines)
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True, [x for x in lines if x.get("ok") is False]
    assert last["attempted"] > 0 and last["failed"] == 0
    env = lines[0]["env"]
    assert {"platform", "device_kind", "device_count", "host_cpu_count"} <= set(env)
    section = "per_layer" if trace else "end_to_end"
    named = {m["name"] for m in BENCH[section] if cell in m.get("workloads", [cell])}
    assert set(last["metrics"]) <= named and last["metrics"]
    if trace:
        assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
        assert len(last["breakdown"]["device_ops"]) <= 10
    else:
        assert "setup_s" in last["metrics"]
    checks = [x for x in lines if "check" in x]
    assert any(x["check"] == "compiles_in_window" and x["value"] == 0 for x in checks)


def test_a_mix_no_cell_uses_runs_under_its_configuration_at_a_given_rate():
    """The rate sweep's path: ``<config>.<mix>`` and ``--rate-per-s``."""
    p = _run(["--workload", "presence-1m-1k.heartbeat-steady", "--seed", "3", "--seconds", "2",
              "--rate-per-s", "40", "--rehearse-on-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] == 80 and last["failed"] == 0


def test_without_a_tpu_nothing_is_printed():
    p = _run(["--workload", BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"])
    assert p.returncode != 0 and p.stdout == ""


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(["--workload", BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
              "--rehearse-on-cpu"], cwd=tmp_path, script=tmp_path / "benchmark" / "run.py",
             env={k: v for k, v in ENV.items() if k != "PYTHONPATH"})
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_a_cell_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """New files under a new directory plus entries in BENCHMARK.json; no
    file of the benchmark is edited."""
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "rio_tpu", tmp_path / "rio_tpu")
    os.symlink(REPO / "native", tmp_path / "native")
    extra = tmp_path / "benchmark_more"
    for sub in ("configs", "traffic", "layers"):
        (extra / sub).mkdir(parents=True)
    conf = json.loads((REPO / "benchmark/configs/presence-1m-1k.json").read_text())
    conf.update(name="presence-tiny", objects=2048, nodes=8, live_servers=2, down_at_start=1,
                rehearsal={})
    (extra / "configs/presence-tiny.json").write_text(json.dumps(conf))
    (extra / "traffic/slow-beats.json").write_text(json.dumps({
        "generators": [{"kind": "open_loop", "name": "beats", "op": "heartbeat",
                        "rate_per_s": 50}],
        "audits_after_window": ["seated", "routed"],
    }))
    (extra / "layers/beats_sent.py").write_text(
        "def read(run):\n    return float(run.log['beats']['ok'].shape[0])\n"
    )
    bench = json.loads(json.dumps(BENCH))
    bench["paths"].append("benchmark_more")
    bench["configs"].append({"name": "presence-tiny", "source": "test",
                             "file": "benchmark_more/configs/presence-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "presence-tiny.slow-beats", "config": "presence-tiny",
                               "traffic": "slow-beats", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "beats_sent", "unit": "requests", "better": "higher",
                               "source": "program_counter", "layer": "client and generator",
                               "moves": "request_p99_ms",
                               "workloads": ["presence-tiny.slow-beats"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = _run(["--workload", "presence-tiny.slow-beats", "--seed", "9", "--seconds", "2",
              "--trace", "1", "--rehearse-on-cpu"], cwd=tmp_path,
             script=tmp_path / "benchmark" / "run.py")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["beats_sent"]["value"] == 100.0
