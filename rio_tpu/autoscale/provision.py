"""NodeProvisioner implementations: where elastic nodes actually come from.

Two real backends ship (both used by the tests, the ``--demo`` smoke, and
the ramp soak):

* :class:`InProcessProvisioner` — new nodes are :class:`~rio_tpu.server.
  Server` instances run as tasks on the calling loop, joining the shared
  membership/placement storages. Zero-process, deterministic, fast: the
  unit/integration tier and the bench A/B use it.
* :class:`SubprocessProvisioner` — new nodes are real OS processes
  (``python -m rio_tpu.autoscale --node``) joining shared sqlite
  storages, the :mod:`rio_tpu.sharded` worker discipline (clean child
  env, JSON spec on stdin, READY line, death-monitor thread marking the
  member inactive). The ramp soak SIGKILLs these mid-drain — the chaos
  case the scale-in state machine must absorb.

A cloud provisioner (ASG/MIG/k8s) implements the same trait; nothing in
the controller knows the difference.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable

from ..sharded import _load_factory, _reserve_port
from . import NodeProvisioner


class InProcessProvisioner(NodeProvisioner):
    """Elastic nodes as server tasks in the current event loop.

    Every provisioned node shares the caller's membership + placement
    storages (the in-process cluster shape of ``tests/server_utils.py``),
    so churn, rebalance, and drain all behave exactly as they do across
    real processes — minus the process boundary.
    """

    def __init__(
        self,
        members_storage: Any,
        placement: Any,
        *,
        registry_builder: Callable[[], Any],
        server_kwargs: dict | None = None,
        app_data_builder: Callable[[], Any] | None = None,
    ) -> None:
        self._members = members_storage
        self._placement = placement
        self._registry_builder = registry_builder
        self._server_kwargs = dict(server_kwargs or {})
        # One AppData per server (a shared instance would collide on the
        # per-node senders the server registers into it); the builder is
        # how chaos tests seat a SHARED state provider on every node.
        self._app_data_builder = app_data_builder
        self._nodes: dict[str, tuple[Any, asyncio.Task]] = {}
        self.provisioned_total = 0
        self.retired_total = 0

    async def provision(self) -> str:
        from ..cluster.membership_protocol import LocalClusterProvider
        from ..server import Server

        kwargs = dict(self._server_kwargs)
        if self._app_data_builder is not None:
            kwargs["app_data"] = self._app_data_builder()
        server = Server(
            address="127.0.0.1:0",
            registry=self._registry_builder(),
            cluster_provider=LocalClusterProvider(self._members),
            object_placement_provider=self._placement,
            **kwargs,
        )
        await server.prepare()
        address = await server.bind()
        task = asyncio.ensure_future(server.run())
        self._nodes[address] = (server, task)
        self.provisioned_total += 1
        return address

    async def retire(self, address: str, *, force: bool = False) -> None:
        server, task = self._nodes.pop(address, (None, None))
        if server is None:
            return
        self.retired_total += 1
        if not task.done():
            if force:
                # Forced retire (drain timed out / victim unresponsive):
                # cut the task — run()'s finally still marks the member
                # inactive and closes the listener.
                task.cancel()
            else:
                # Normally the drain already stopped the node; a straggler
                # gets the graceful path rather than a cancel.
                from ..commands import AdminCommand

                server.admin_sender().send(AdminCommand.drain())
        # A forced retire cancelled the task above — shield re-raises that
        # CancelledError here, so it must be suppressed alongside Exception.
        with contextlib.suppress(Exception, asyncio.CancelledError):
            await asyncio.wait_for(asyncio.shield(task), timeout=10.0)
        if not task.done():
            task.cancel()
        with contextlib.suppress(Exception):
            await asyncio.gather(task, return_exceptions=True)
        # Converge membership (the SubprocessProvisioner monitor-thread
        # contract): the node's own teardown set_inactive may have failed —
        # e.g. killed during a storage outage — and a retired-but-"active"
        # member pins its directory rows to a dead address until the
        # heartbeat TTL ages out.
        host, _, port = address.rpartition(":")
        with contextlib.suppress(Exception):
            await self._members.set_inactive(host, int(port))

    def managed(self) -> list[str]:
        return list(self._nodes)

    def server(self, address: str) -> Any:
        """Test hook: the live Server behind a managed address."""
        entry = self._nodes.get(address)
        return entry[0] if entry else None

    def kill(self, address: str) -> None:
        """Chaos hook: abrupt death (the in-process analogue of SIGKILL) —
        cancel the serve task with no drain; the run() teardown marks the
        member inactive just as the sharded monitor thread would."""
        entry = self._nodes.get(address)
        if entry is not None:
            entry[1].cancel()


class SubprocessProvisioner(NodeProvisioner):
    """Elastic nodes as real worker processes over shared sqlite storage.

    The :mod:`rio_tpu.sharded` worker discipline, minus the fixed-width
    shard map: reserve an ephemeral identity port, spawn ``python -m
    rio_tpu.autoscale --node`` with a clean environment and a JSON spec on
    stdin, wait for the address to turn active in shared membership, and
    run a monitor thread that marks the member inactive the moment the
    process dies (the supervisor half of crash reseat — and what turns a
    mid-drain SIGKILL into the dead-owner branch on the survivors).
    """

    def __init__(
        self,
        data_dir: str,
        *,
        registry: str = "rio_tpu.utils.routing_live:build_echo_registry",
        members: str = "rio_tpu.sharded:sqlite_members",
        placement: str = "rio_tpu.sharded:sqlite_placement",
        state: str = "",
        host: str = "127.0.0.1",
        server_kwargs: dict | None = None,
        python: str = sys.executable,
        ready_timeout: float = 60.0,
    ) -> None:
        self.data_dir = data_dir
        self.registry_spec = registry
        self.members_spec = members
        self.placement_spec = placement
        # Optional shared StateProvider factory ("module:callable" over
        # data_dir): with it, acked writes survive a SIGKILLed node — the
        # reseated actor reloads at activation (the soak's zero-loss bar).
        self.state_spec = state
        self.host = host
        self.server_kwargs = dict(server_kwargs or {})
        self.python = python
        self.ready_timeout = ready_timeout
        self._procs: dict[str, subprocess.Popen] = {}
        self._logs: dict[str, Any] = {}
        self._reservations: dict[str, Any] = {}
        self._retiring: set[str] = set()
        self.provisioned_total = 0
        self.retired_total = 0

    def _child_env(self) -> dict:
        # Clean environment, and always the CPU platform: elastic workers
        # never import jax, and the chip belongs to the process that owns
        # the directory (same rule as ShardedServer._child_env).
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        return {
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", "/tmp"),
            "PYTHONPATH": repo_root,
            "JAX_PLATFORMS": "cpu",
        }

    async def provision(self) -> str:
        reservation, port = _reserve_port(self.host)
        address = f"{self.host}:{port}"
        spec = {
            "bind_host": self.host,
            "identity_port": port,
            "advertise": address,
            "reuse_port": reservation is not None,
            "registry": self.registry_spec,
            "members": self.members_spec,
            "placement": self.placement_spec,
            "state": self.state_spec,
            "data_dir": self.data_dir,
            "server_kwargs": self.server_kwargs,
        }
        log_f = open(
            os.path.join(self.data_dir, f"autoscale-node-{port}.log"), "wb"
        )
        proc = subprocess.Popen(
            [self.python, "-m", "rio_tpu.autoscale", "--node"],
            stdin=subprocess.PIPE,
            stdout=log_f,
            stderr=subprocess.STDOUT,
            env=self._child_env(),
            close_fds=True,
        )
        assert proc.stdin is not None
        proc.stdin.write(json.dumps(spec).encode())
        proc.stdin.close()
        self._procs[address] = proc
        self._logs[address] = log_f
        if reservation is not None:
            self._reservations[address] = reservation
        try:
            await self._wait_active(address, proc)
        except Exception:
            with contextlib.suppress(Exception):
                proc.kill()
            self._drop(address)
            raise
        threading.Thread(
            target=self._monitor, args=(address, proc), daemon=True
        ).start()
        self.provisioned_total += 1
        return address

    async def _wait_active(self, address: str, proc: subprocess.Popen) -> None:
        members = _load_factory(self.members_spec)(self.data_dir)
        try:
            await members.prepare()
            deadline = time.monotonic() + self.ready_timeout
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"autoscale node {address} died during boot "
                        f"(rc={proc.returncode}); see its log in {self.data_dir}"
                    )
                active = {m.address for m in await members.active_members()}
                if address in active:
                    return
                await asyncio.sleep(0.05)
            raise TimeoutError(
                f"autoscale node {address} not active within "
                f"{self.ready_timeout}s"
            )
        finally:
            with contextlib.suppress(Exception):
                members.close()

    def _monitor(self, address: str, proc: subprocess.Popen) -> None:
        """Mark a dead node inactive in membership (supervisor half of the
        crash-reseat story; idempotent beside a graceful self-mark)."""
        proc.wait()
        if address in self._retiring:
            return
        with contextlib.suppress(Exception):
            asyncio.run(self._mark_inactive(address))

    async def _mark_inactive(self, address: str) -> None:
        members = _load_factory(self.members_spec)(self.data_dir)
        try:
            host, _, port = address.rpartition(":")
            await members.set_inactive(host, int(port))
        finally:
            with contextlib.suppress(Exception):
                members.close()

    def terminate(self, address: str, sig: int = signal.SIGKILL) -> None:
        """Chaos hook: kill a managed node (default SIGKILL — the monitor
        thread records the death in membership as for a real crash)."""
        proc = self._procs.get(address)
        if proc is not None:
            with contextlib.suppress(ProcessLookupError):
                proc.send_signal(sig)

    async def retire(self, address: str, *, force: bool = False) -> None:
        proc = self._procs.get(address)
        if proc is None:
            return
        self._retiring.add(address)
        self.retired_total += 1
        try:
            if force and proc.poll() is None:
                with contextlib.suppress(ProcessLookupError):
                    proc.send_signal(signal.SIGTERM)
            # A drained node exits by itself; give it (or the SIGTERM
            # drain handler) a bounded window, then escalate.
            deadline = time.monotonic() + 10.0
            while proc.poll() is None and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            if proc.poll() is None:
                with contextlib.suppress(ProcessLookupError):
                    proc.kill()
                while proc.poll() is None:
                    await asyncio.sleep(0.05)
            await self._mark_inactive(address)
        finally:
            self._drop(address)

    def _drop(self, address: str) -> None:
        self._procs.pop(address, None)
        log_f = self._logs.pop(address, None)
        if log_f is not None:
            with contextlib.suppress(OSError):
                log_f.close()
        res = self._reservations.pop(address, None)
        if res is not None:
            with contextlib.suppress(OSError):
                res.close()

    def managed(self) -> list[str]:
        return list(self._procs)

    def node_log(self, address: str) -> str:
        _, _, port = address.rpartition(":")
        path = os.path.join(self.data_dir, f"autoscale-node-{port}.log")
        try:
            with open(path, "rb") as f:
                return f.read().decode(errors="replace")
        except OSError:
            return ""
