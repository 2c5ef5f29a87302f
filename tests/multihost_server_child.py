"""Child server process for the cross-process migration test.

Each invocation boots ONE real rio-tpu server on the given port, joined to
its sibling through sqlite membership + placement files in the shared
directory — the cross-process analog of the in-process integration harness.
Run by tests/test_multihost.py with a clean env (PYTHONPATH=<repo> only).
It never imports jax; the platform is pinned to CPU all the same.
"""

import asyncio
import os
import sys

port, dbdir = sys.argv[1], sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rio_tpu import Server  # noqa: E402
from rio_tpu.cluster.membership_protocol import LocalClusterProvider  # noqa: E402
from rio_tpu.cluster.storage.sqlite import SqliteMembershipStorage  # noqa: E402
from rio_tpu.object_placement.sqlite import SqliteObjectPlacement  # noqa: E402
from tests.multihost_actor import build_registry  # noqa: E402


async def main() -> None:
    members = SqliteMembershipStorage(os.path.join(dbdir, "members.db"))
    placement = SqliteObjectPlacement(os.path.join(dbdir, "placement.db"))
    server = Server(
        address=f"127.0.0.1:{port}",
        registry=build_registry(),
        cluster_provider=LocalClusterProvider(members),
        object_placement_provider=placement,
    )
    await server.prepare()
    await server.bind()
    print("READY", flush=True)
    await server.run()


asyncio.run(main())
