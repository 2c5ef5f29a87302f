"""What holds since the served path was brought up on the chip (ISSUE 21).

No fallback hides the device, one process owns the chip, the compile cache
can be placed from outside, the native library is trusted by content, and
``chip_smoke.py`` refuses to run without an accelerator unless it is told,
explicitly, to rehearse on the CPU.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from rio_tpu.utils import jaxenv  # noqa: E402

# ---------------------------------------------------------------------------
# Compile cache: placed from outside, or at one fixed path
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_config():
    """Restore jax's cache directory setting after a test moved it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_from_env_means_no_code_sets_one(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    jaxenv.compile_cache_dir()
    # jax reads the variable itself (at import); the helper touched nothing.
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_unset_is_the_checkout_on_an_accelerator(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = str(REPO / ".jax_cache")
    assert jaxenv.compile_cache_dir() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jaxenv.compile_cache_dir() == want  # same answer on a second call


def test_cache_dir_does_not_depend_on_the_working_directory(
    monkeypatch, cache_config, tmp_path
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.chdir(tmp_path)
    first = jaxenv.compile_cache_dir()
    (tmp_path / "elsewhere").mkdir()
    monkeypatch.chdir(tmp_path / "elsewhere")
    assert jaxenv.compile_cache_dir() == first == str(REPO / ".jax_cache")


def test_cpu_backend_gets_no_cache_and_the_suite_leaves_none(monkeypatch):
    """Host compiles are not cached (unset variable): the suite runs a
    directory solve here and the checkout gains no cache directory."""
    import asyncio

    from rio_tpu import ObjectId
    from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jaxenv.compile_cache_dir() is None

    async def solve():
        p = JaxObjectPlacement(mode="sinkhorn")
        p.sync_members([f"10.1.0.{i}:70" for i in range(4)])
        await p.assign_batch([ObjectId("T", str(i)) for i in range(64)])
        await p.rebalance(delta=False)
        return p.stats

    stats = asyncio.run(solve())
    assert stats.mode == "sinkhorn+collapsed"
    assert not jax.config.jax_compilation_cache_dir
    assert not (REPO / ".jax_cache").exists()


def test_directory_places_the_cache_at_a_solve_not_in_the_constructor(monkeypatch):
    import asyncio

    from rio_tpu import ObjectId
    from rio_tpu.object_placement import jax_placement as jp

    calls = []
    monkeypatch.setattr(jp, "compile_cache_dir", lambda: calls.append(1))
    p = jp.JaxObjectPlacement()
    p.sync_members(["10.1.0.1:70", "10.1.0.2:70"])
    assert calls == []  # a constructor must not touch the backend
    asyncio.run(p.assign_batch([ObjectId("T", "a")]))
    assert calls  # before the first compile


# ---------------------------------------------------------------------------
# The compile/execute split is measured, not guessed
# ---------------------------------------------------------------------------


def test_solve_stats_carry_a_compile_exec_split():
    import asyncio

    from rio_tpu import ObjectId
    from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

    async def solve():
        p = JaxObjectPlacement(mode="sinkhorn", n_iters=7)  # a fresh trace
        p.sync_members([f"10.2.0.{i}:70" for i in range(5)])
        await p.assign_batch([ObjectId("T", str(i)) for i in range(100)])
        await p.rebalance(delta=False)
        first = p.stats
        await p.rebalance(delta=False)
        return first, p.stats

    first, second = asyncio.run(solve())
    assert first.compile_ms > 0.0 and first.exec_ms > 0.0
    assert first.compile_ms <= first.solve_ms
    assert 0.0 <= second.compile_ms < first.compile_ms


def test_cache_time_saved_is_not_counted_as_compile_time():
    """jax reports a persistent-cache hit's SAVED seconds as a duration
    event; summing it would make a warm run look like a cold one."""
    from rio_tpu.object_placement import jax_placement as jp

    before = jp._compile_seconds()
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 100.0
    )
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 100.0
    )
    assert jp._compile_seconds() == before
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 2.5
    )
    assert jp._compile_seconds() == pytest.approx(before + 2.5)


def test_another_threads_compile_stays_out_of_this_solves_window():
    """N daemons on one provider solve concurrently, each in its own
    worker thread; a process-wide total put a sibling's compile into every
    open window (on the chip: compile_ms 576 of solve_ms 567)."""
    import threading

    from rio_tpu.object_placement import jax_placement as jp

    before = jp._compile_seconds()

    def sibling():
        jp._compile_seconds()
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 7.0
        )
        seen.append(jp._compile_seconds())

    seen: list = []
    t = threading.Thread(target=sibling)
    t.start()
    t.join()
    assert seen == [7.0]  # counted where it ran ...
    assert jp._compile_seconds() == before  # ... and nowhere else


def test_solve_window_reports_a_negative_split_instead_of_clamping_it():
    from rio_tpu.object_placement import jax_placement as jp

    c0 = jp._compile_seconds()
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 5.0
    )
    ms = 1.25  # the solve.device stage's wall time: far less than the compile
    conv = jp._conv_timing({}, ms, c0)
    assert conv["compile_ms"] == pytest.approx(5000.0)
    assert conv["exec_ms"] == pytest.approx(ms - 5000.0, abs=1e-2) and conv["exec_ms"] < 0


# ---------------------------------------------------------------------------
# One process per chip
# ---------------------------------------------------------------------------


def test_sharded_workers_get_cpu_whatever_the_parent_has(monkeypatch, tmp_path):
    from rio_tpu.sharded import ShardedServer

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    node = ShardedServer(
        address="127.0.0.1:0", workers=2,
        registry="tests.sharded_actors:build_registry", data_dir=str(tmp_path),
    )
    env = node._child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "JAX_COMPILATION_CACHE_DIR" not in env


def test_provisioned_nodes_get_cpu_whatever_the_parent_has(monkeypatch, tmp_path):
    from rio_tpu.autoscale.provision import SubprocessProvisioner

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    prov = SubprocessProvisioner(
        registry="tests.sharded_actors:build_registry", data_dir=str(tmp_path)
    )
    assert prov._child_env()["JAX_PLATFORMS"] == "cpu"


# ---------------------------------------------------------------------------
# No interpret mode unasked, no CPU pin unasked
# ---------------------------------------------------------------------------


def _tiny_problem():
    cost = jax.random.uniform(jax.random.PRNGKey(0), (16, 128), jnp.float32)
    return cost, jnp.ones((16,), jnp.float32), jnp.ones((128,), jnp.float32)


@pytest.mark.parametrize("name", ["pallas_scaling_core", "pallas_scaling_sinkhorn"])
def test_scaling_kernel_without_interpret_fails_loudly_on_cpu(name):
    from rio_tpu.ops import scaling

    cost, mass, cap = _tiny_problem()
    with pytest.raises(ValueError, match="interpret mode"):
        out = getattr(scaling, name)(cost, mass, cap, n_iters=2, block_rows=8)
        jax.block_until_ready(out)


def test_logdomain_kernel_without_interpret_fails_loudly_on_cpu():
    from rio_tpu.ops.pallas_sinkhorn import pallas_sinkhorn

    cost, mass, cap = _tiny_problem()
    with pytest.raises(ValueError, match="interpret mode"):
        jax.block_until_ready(pallas_sinkhorn(cost, mass, cap, n_iters=2, block_rows=8))


def test_entry_builds_on_the_backend_the_process_has(monkeypatch):
    import __graft_entry__ as graft

    def no_pin(*_a, **_kw):
        raise AssertionError("entry() must not pin the CPU")

    monkeypatch.setattr(jaxenv, "force_cpu", no_pin)
    fn, args = graft.entry()
    assert {a.devices().pop().platform for a in args} == {jax.default_backend()}
    assert not hasattr(graft, "_tpu_usable")


# ---------------------------------------------------------------------------
# Fresh machines: clocks that count from boot, trees that were copied
# ---------------------------------------------------------------------------


def test_first_tick_samples_on_a_host_younger_than_the_interval(monkeypatch):
    from rio_tpu import timeseries

    monkeypatch.setattr(timeseries.time, "monotonic", lambda: 12.0)  # just booted
    series = timeseries.GaugeSeries(interval=3600.0)
    assert series.tick(lambda: {"g": 1.0}) is not None
    assert series.tick(lambda: {"g": 2.0}) is None  # then rate-limited


def _fake_native_tree(monkeypatch, tmp_path):
    """Point the loader at a private copy of the native source tree (for
    ``_ensure_built`` only: ``get()`` would cache what it finds there)."""
    from rio_tpu import native

    src = tmp_path / "rio_native.cc"
    src.write_bytes((REPO / "native" / "rio_native.cc").read_bytes())
    monkeypatch.setattr(native, "_SRC", src)
    monkeypatch.setattr(native, "_SO", tmp_path / "librio_native.so")
    monkeypatch.setattr(native, "_SO_DIGEST", tmp_path / "librio_native.so.sha256")
    monkeypatch.delenv("RIO_TPU_NATIVE_LIB", raising=False)
    return native, src


def test_native_library_is_trusted_by_content_not_mtime(monkeypatch, tmp_path):
    import shutil

    native, src = _fake_native_tree(monkeypatch, tmp_path)
    if shutil.which(os.environ.get("CXX", "g++")) is None:
        pytest.skip("no C++ compiler here")
    path, status = native._ensure_built()
    assert status == "built" and path.exists()
    assert native._ensure_built()[1] == "loaded"
    # A copied tree: the library is NEWER than the source yet built from
    # something else. The old mtime rule trusted it.
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    os.utime(src, (time.time() - 3600, time.time() - 3600))
    assert native._ensure_built()[1] == "built"
    # No recorded digest: origin unknown, rebuilt.
    (tmp_path / "librio_native.so.sha256").unlink()
    assert native._ensure_built()[1] == "built"


def test_native_build_failure_is_readable(monkeypatch, tmp_path):
    native, _src = _fake_native_tree(monkeypatch, tmp_path)
    monkeypatch.setenv("CXX", str(tmp_path / "no-such-compiler"))
    path, status = native._ensure_built()
    assert path is None and status.startswith("absent: build failed")
    assert not list(tmp_path.glob("*.tmp"))


def test_native_status_says_what_get_did():
    from rio_tpu import native

    lib, status = native.get(), native.status()
    if lib is None:
        assert status.startswith("absent: ") and len(status) > len("absent: ")
    else:
        assert status in ("built", "loaded")


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def _smoke(*argv, cwd=REPO, script=REPO / "chip_smoke.py", timeout=240, env=None):
    return subprocess.run(
        [sys.executable, str(script), *argv], cwd=str(cwd), capture_output=True,
        text=True, timeout=timeout, env=env,
    )


def test_smoke_quota_check_follows_the_capacities_the_solve_was_given():
    """Servers booted with defaults measure their own load and the
    directory derates them; a check that assumed capacity 1.0 everywhere
    failed the default wiring on the chip."""
    import importlib.util

    import numpy as np

    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    # 4 nodes, one derated to 0.5, one dead: shares 400/200/400 of 1000.
    cap = np.array([1.0, 0.5, 1.0, 0.0])
    seats = np.repeat([0, 1, 2], [400, 200, 400])
    out = smoke._check_directory(seats, cap, 0)
    assert out["overflow"] == out["underflow"] == 0 and out["derated_nodes"] == 1
    with pytest.raises(AssertionError, match="directory check failed"):
        smoke._check_directory(np.repeat([0, 1, 2], [334, 333, 333]), cap, 0)
    with pytest.raises(AssertionError, match="directory check failed"):
        smoke._check_directory(np.repeat([0, 1, 3], [500, 250, 250]), cap, 0)
    # The greedy mode promises the ceiling only; the two-level solve is
    # allowed its slack on both sides.
    wide = np.array([1.0] * 10 + [0.5])  # shares 95.62 x 10 and 47.81 of 1004
    short = np.repeat(np.arange(11), [96] * 10 + [44])
    assert smoke._check_directory(short, wide, 0, exact=False)["underflow"] == 3
    assert smoke._check_directory(short, wide, 3)["underflow"] == 0
    with pytest.raises(AssertionError, match="directory check failed"):
        smoke._check_directory(short, wide, 0)


def test_smoke_without_an_accelerator_fails_fast_and_prints_no_metric():
    t0 = time.monotonic()
    proc = _smoke(timeout=60)
    assert proc.returncode not in (0, None)
    assert proc.stdout == ""
    assert "not 'tpu'" in proc.stderr
    assert time.monotonic() - t0 < 30.0


def test_smoke_alone_in_a_directory_fails_and_prints_nothing(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes((REPO / "chip_smoke.py").read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _smoke("--rehearse-on-cpu", cwd=tmp_path, script=alone, timeout=60, env=env)
    assert proc.returncode != 0 and proc.stdout.count('"ok"') == 0


def test_smoke_rehearsal_on_cpu_runs_every_phase_and_says_so(tmp_path):
    env = dict(os.environ)
    env["RIO_TPU_HIER_CHUNK_ROWS"] = "1024"  # the chunked route, at this size
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = _smoke(
        "--rehearse-on-cpu", "--objects", "2048", "--nodes", "16",
        "--servers", "3", "--requests", "24", "--churn-nodes", "2",
        "--kernel-rows", "64", "--kernel-cols", "128",
        "--mesh-objects", "16384", "--mesh-nodes", "16", env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    assert all(x["rehearsal"] is True and x["platform"] == "cpu" for x in lines)
    assert lines[-1]["ok"] is True and lines[-1]["device"]["platform"] == "cpu"
    phases = {x["phase"]: x for x in lines if "phase" in x}
    for name in ("requests_before_churn", "requests_after_resolves"):
        assert phases[name]["failed"] == 0 and phases[name]["misrouted"] == 0
    moved = phases["requests_after_churn"]["moved_onto_live"]
    assert moved["sent"] > 0 and moved["failed"] == moved["misrouted"] == 0
    churn = phases["churn_daemon_delta"]
    assert churn["daemon_delta_rebalances"] >= 1 and churn["undisplaced_moved"] == 0
    assert phases["solve_full_collapsed"]["mode"] == "sinkhorn+collapsed"
    assert phases["solve_hierarchical"]["chunks"] == 2
    assert phases["kernel_fused_scaling"]["interpret"] is True
    for rec in phases.values():
        assert rec.get("directory", {}).get("overflow", 0) == 0
    # conftest's 8 virtual CPU devices are inherited: the mesh phase ran.
    assert phases["mesh_chunk_solve"]["mode"] == "hierarchical+mesh_chunk"
    assert phases["mesh_chunk_solve"]["devices"] == len(jax.devices())
    assert len(set(phases["mesh_result_shards"]["shard_devices"])) == len(jax.devices())
    # The cache is where the variable says and nowhere else.
    assert phases["env"]["compile_cache_dir"] == str(tmp_path / "cache")
    assert not (REPO / ".jax_cache").exists()
