"""Multi-host placement solve: the same program from 1 chip to a pod.

The SPMD bring-up recipe for the solver plane (see
``rio_tpu/parallel/multihost.py``). Run it three ways — the PROGRAM TEXT
is identical in all of them, which is the point:

1. Single process (laptop / one chip)::

       python examples/multihost_solve.py

2. Two processes on one machine (real multi-controller over loopback —
   what tests/test_multihost.py does)::

       python examples/multihost_solve.py --coordinator 127.0.0.1:9911 \
           --num-processes 2 --process-id 0 &
       python examples/multihost_solve.py --coordinator 127.0.0.1:9911 \
           --num-processes 2 --process-id 1

3. A TPU pod (one process per host; the pod runtime supplies the cluster
   env, so no arguments are needed)::

       python examples/multihost_solve.py   # on every host

Where the reference stack would initialize NCCL/MPI communicators and
hand-shard tensors, here :func:`multihost.initialize` joins the hosts into
one jax runtime and the SAME ``shard_map`` solve spans all of them — XLA
routes the collectives (ICI in-slice, DCN across).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", default=None, help="host:port of process 0")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--objects-per-device", type=int, default=4096)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from rio_tpu.parallel import make_mesh, multihost
    from rio_tpu.parallel.hierarchical import sharded_hierarchical_assign

    multi = multihost.initialize(
        args.coordinator, args.num_processes, args.process_id
    )
    if not multi and args.coordinator is None:
        # Single-process demo (initialize() found no cluster and touched
        # no backend): this example is about the SPMD structure, so it
        # runs on 8 virtual CPU devices — one chip would make a mesh of
        # one. The accelerator demos are the other examples.
        from rio_tpu.utils.jaxenv import force_cpu

        force_cpu(n_devices=8)
    me = jax.process_index()
    print(
        f"[host {me}] processes={jax.process_count()} "
        f"global_devices={jax.device_count()} local={jax.local_device_count()} "
        f"(multihost={multi})"
    )

    mesh = make_mesh()  # spans every host's devices
    n_obj = args.objects_per_device * jax.device_count()
    d, m, g = 16, 64, 8
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    # Every host derives the same global inputs, then feeds ONLY its rows
    # (in production these rows come from the host's own directory shard).
    obj_all = np.asarray(jax.random.normal(k1, (n_obj, d), jnp.float32))
    node_feat = np.asarray(jax.random.normal(k2, (d, m), jnp.float32)) * 0.2
    rows = multihost.process_rows(n_obj, mesh)
    axes = tuple(mesh.axis_names)
    obj_feat = multihost.distributed_array(mesh, P(axes, None), obj_all[rows])
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[5].set(0.0)  # one dead node

    res = sharded_hierarchical_assign(
        mesh, obj_feat, node_feat, cap, alive, n_groups=g
    )
    jax.block_until_ready(res.assignment)

    from jax.experimental import multihost_utils

    if jax.process_count() > 1:
        a = np.asarray(
            multihost_utils.process_allgather(res.assignment, tiled=True)
        )
    else:
        a = np.asarray(res.assignment)
    loads = np.bincount(a, minlength=m)
    print(
        f"[host {me}] placed {n_obj} objects on {m - 1} live nodes: "
        f"load min/max = {loads[loads > 0].min()}/{loads.max()}, "
        f"dead-node load = {loads[5]}, overflow = {int(res.overflow)}"
    )
    assert loads[5] == 0 and int(res.overflow) == 0


if __name__ == "__main__":
    main()
