"""Multi-host bring-up helpers, single-process degradation contract.

A real multi-controller run needs N processes (impossible in this image);
what IS testable — and what the bring-up recipe relies on — is that every
helper degrades to the exact local equivalent in one process, so the same
program text runs on a laptop, one chip, and a pod.
"""

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from rio_tpu.parallel import make_mesh
from rio_tpu.parallel import multihost


def test_initialize_is_noop_with_backend_already_up(monkeypatch):
    """In a long-lived single process (this test runner: conftest booted
    the backend long ago), an env-driven initialize() stays single-process
    via the RuntimeError 'before' branch instead of raising."""
    for k in (
        "JAX_COORDINATOR_ADDRESS",
        "COORDINATOR_ADDRESS",
        "TPU_WORKER_HOSTNAMES",
        "SLURM_JOB_ID",
    ):
        monkeypatch.delenv(k, raising=False)
    assert multihost.initialize() is False
    assert multihost.is_multihost() is False


def test_initialize_treats_no_cluster_valueerror_as_single_process(monkeypatch):
    """Fresh-process path: jax's cluster auto-detection raising its
    'coordinator_address should be defined' ValueError means "no cluster",
    not an error — pin the message-match against jax upgrades."""
    monkeypatch.setattr(multihost, "_already_initialized", lambda: False)

    def fake_initialize(**kw):
        raise ValueError("coordinator_address should be defined.")

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    assert multihost.initialize() is False
    # Any EXPLICIT multi-process intent with the same failure is a real
    # error — a launcher passing world size but missing the coordinator
    # must not silently run as 1 of 1.
    with pytest.raises(ValueError):
        multihost.initialize("127.0.0.1:1", num_processes=2, process_id=0)
    with pytest.raises(ValueError):
        multihost.initialize(None, num_processes=2, process_id=0)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_process_rows_covers_everything_single_process():
    mesh = make_mesh(jax.devices()[:8])
    n = 64 * mesh.shape["obj"]
    rows = multihost.process_rows(n, mesh)
    # One process owns every shard.
    assert (rows.start, rows.stop) == (0, n)


@pytest.mark.slow
def test_two_process_multicontroller_solve_parity(tmp_path):
    """REAL multi-controller run: two OS processes, 2 CPU devices each,
    joined by jax.distributed over loopback (gloo — the DCN analog), one
    4-device mesh spanning both. Each process feeds only ITS object rows
    via distributed_array; the sharded hierarchical solve runs with real
    cross-process collectives; the gathered global assignment must EQUAL
    the single-process per-shard reference (the same mechanism-parity
    standard as the dryrun) and avoid the dead node."""
    import socket
    import subprocess
    import sys as _sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = str(Path(__file__).resolve().parent.parent)
    child = str(Path(__file__).resolve().parent / "multihost_child.py")
    env = {
        # A clean env; the child pins its own platform (CPU).
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/tmp"),
        "PYTHONPATH": repo,
    }
    procs = [
        subprocess.Popen(
            [_sys.executable, child, str(pid), "2", str(port), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out.decode(errors="replace"))
    finally:
        for p in procs:
            p.kill()
    assert all(p.returncode == 0 for p in procs), outs
    a = np.load(tmp_path / "assignment.npy")
    overflow, n_shards = np.load(tmp_path / "meta.npy").tolist()
    assert a.shape == (256,) and overflow == 0 and n_shards == 4
    assert not (a == 3).any(), "dead node attracted objects"
    # Mechanism parity: the cross-process solve must equal the concat of
    # per-shard local solves on identical inputs (shard-local by design).
    import jax.numpy as jnp

    from rio_tpu.parallel.hierarchical import hierarchical_assign

    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    obj_all = np.asarray(jax.random.normal(k1, (256, 8), jnp.float32))
    node_feat = np.asarray(jax.random.normal(k2, (8, 16), jnp.float32)) * 0.2
    cap = jnp.ones((16,), jnp.float32)
    alive = jnp.ones((16,), jnp.float32).at[3].set(0.0)
    shard = 256 // n_shards
    ref = np.concatenate(
        [
            np.asarray(
                hierarchical_assign(
                    obj_all[k * shard : (k + 1) * shard], node_feat, cap,
                    alive, n_groups=4, coarse_iters=8, fine_iters=8,
                ).assignment
            )
            for k in range(n_shards)
        ]
    )
    # EXACT equality (same numerics on the CPU children): the docs claim
    # exact mechanism parity, so the test must hold exactly that.
    np.testing.assert_array_equal(a, ref)


def test_cross_process_migration_installs_state_over_sockets(tmp_path):
    """REAL cross-process migration: two server OS processes joined only by
    sqlite membership/placement files, a client in the parent. A volatile
    counter (no persisted state) is seated on one process, migrated to the
    other via MigrateObject to the node-scoped control actor, and must
    arrive with its in-memory value intact — proving the inline
    InstallState transfer ran over real sockets between real processes."""
    import asyncio
    import socket
    import subprocess
    import sys as _sys

    ports = []
    socks = []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    repo = str(Path(__file__).resolve().parent.parent)
    child = str(Path(__file__).resolve().parent / "multihost_server_child.py")
    env = {
        # Clean env; the children pin the CPU platform themselves.
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/tmp"),
        "PYTHONPATH": repo,
    }
    procs = [
        subprocess.Popen(
            [_sys.executable, child, str(port), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        )
        for port in ports
    ]
    addrs = [f"127.0.0.1:{p}" for p in ports]

    async def drive():
        from rio_tpu import Client
        from rio_tpu.cluster.storage.sqlite import SqliteMembershipStorage
        from rio_tpu.migration import CONTROL_TYPE, MigrateObject, MigrationAck
        from rio_tpu.object_placement.sqlite import SqliteObjectPlacement

        from .multihost_actor import Bump, Get, MhCounter, Val

        members = SqliteMembershipStorage(str(tmp_path / "members.db"))
        placement = SqliteObjectPlacement(str(tmp_path / "placement.db"))
        try:
            deadline = asyncio.get_event_loop().time() + 60.0
            while asyncio.get_event_loop().time() < deadline:
                if any(p.poll() is not None for p in procs):
                    raise AssertionError("a server child exited early")
                try:
                    active = {m.address for m in await members.active_members()}
                except Exception:
                    active = set()
                if set(addrs) <= active:
                    break
                await asyncio.sleep(0.1)
            else:
                raise TimeoutError("children never became active members")

            client = Client(members)
            try:
                out = await client.send(MhCounter, "m1", Bump(amount=7), returns=Val)
                assert out.hot == 7 and out.address in addrs
                source = out.address
                target = next(a for a in addrs if a != source)

                ack = await client.send(
                    CONTROL_TYPE,
                    source,
                    MigrateObject(
                        type_name="MhCounter", object_id="m1", target=target
                    ),
                    returns=MigrationAck,
                )
                assert ack.ok, ack.detail

                # Directory flipped in the shared sqlite placement.
                from rio_tpu.registry import ObjectId

                assert await placement.lookup(ObjectId("MhCounter", "m1")) == target

                # The next request reactivates on the target with the
                # volatile value intact — only InstallState could carry it.
                out = await client.send(MhCounter, "m1", Get(), returns=Val)
                assert out.address == target
                assert out.hot == 7
                out = await client.send(MhCounter, "m1", Bump(amount=1), returns=Val)
                assert (out.address, out.hot) == (target, 8)
            finally:
                client.close()
        finally:
            members.close()
            placement.close()

    try:
        asyncio.run(drive())
    finally:
        outs = []
        for p in procs:
            p.kill()
            out, _ = p.communicate(timeout=30)
            outs.append(out.decode(errors="replace"))
        # Surface child logs on any failure for debuggability.
        del outs


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_distributed_array_matches_device_put_and_feeds_solver():
    mesh = make_mesh(jax.devices()[:8])
    n_obj = 64 * mesh.shape["obj"]
    rows = multihost.process_rows(n_obj, mesh)
    local = np.arange(n_obj * 4, dtype=np.float32).reshape(n_obj, 4)[rows]
    arr = multihost.distributed_array(mesh, P("obj", None), local)
    assert arr.shape == (n_obj, 4)
    np.testing.assert_array_equal(np.asarray(arr), local)
    # And it is genuinely sharded input for the mesh solvers.
    from rio_tpu.parallel.hierarchical import sharded_hierarchical_assign

    d, m, g = 4, 16, 4
    node_feat = jnp.ones((d, m), jnp.float32) * 0.1
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32)
    res = sharded_hierarchical_assign(
        mesh, arr, node_feat, cap, alive, n_groups=g,
        coarse_iters=4, fine_iters=4,
    )
    a = np.asarray(res.assignment)
    assert a.shape == (n_obj,)
    assert a.min() >= 0 and a.max() < m
