"""Child program for the REAL two-process multi-controller test.

Each process runs this same program (the SPMD contract): pin 2 local CPU
devices, join the cluster via rio_tpu.parallel.multihost.initialize, build
the global 4-device mesh, feed ONLY this process's object rows, solve, and
gather the global assignment. Process 0 writes the artifacts the parent
test asserts on.

Run by tests/test_multihost.py with a clean environment; the child pins
its own platform (CPU: a chip belongs to one process, and there are two
of these).
"""

import os
import sys

pid, nproc, port, outdir = (
    int(sys.argv[1]),
    int(sys.argv[2]),
    sys.argv[3],
    sys.argv[4],
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from rio_tpu.parallel import make_mesh, multihost  # noqa: E402

ok = multihost.initialize(
    f"127.0.0.1:{port}", num_processes=nproc, process_id=pid
)
assert ok and multihost.is_multihost(), (ok, jax.process_count())
assert jax.device_count() == 2 * nproc

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from rio_tpu.parallel.hierarchical import sharded_hierarchical_assign  # noqa: E402

N_OBJ, D, M, G = 256, 8, 16, 4
DEAD = 3

mesh = make_mesh()  # spans every process's devices
key = jax.random.PRNGKey(3)
k1, k2 = jax.random.split(key)
# Deterministic global inputs: every process derives identical arrays and
# feeds only its own rows.
obj_all = np.asarray(jax.random.normal(k1, (N_OBJ, D), jnp.float32))
node_feat = np.asarray(jax.random.normal(k2, (D, M), jnp.float32)) * 0.2
rows = multihost.process_rows(N_OBJ, mesh)
axes = tuple(mesh.axis_names)
obj_feat = multihost.distributed_array(mesh, P(axes, None), obj_all[rows])
cap = jnp.ones((M,), jnp.float32)
alive = jnp.ones((M,), jnp.float32).at[DEAD].set(0.0)
res = sharded_hierarchical_assign(
    mesh, obj_feat, node_feat, cap, alive,
    n_groups=G, coarse_iters=8, fine_iters=8,
)
assignment = multihost_utils.process_allgather(res.assignment, tiled=True)
if pid == 0:
    np.save(os.path.join(outdir, "assignment.npy"), np.asarray(assignment))
    np.save(
        os.path.join(outdir, "meta.npy"),
        np.asarray([int(res.overflow), mesh.shape["obj"] * mesh.shape["node"]]),
    )
print(f"[{pid}] done", flush=True)
