"""Multi-host bring-up for the solver plane (SPMD over ICI + DCN).

The reference's cross-host scaling story is tokio TCP + SQL rendezvous for
the control plane and nothing for compute (``rio-rs/src/service.rs:370-378``
is its transport ceiling). rio-tpu's compute plane scales the TPU way
instead: every host runs the SAME program, :func:`initialize` wires the
hosts into one multi-controller jax runtime, and the mesh/shard_map code in
:mod:`rio_tpu.parallel` then spans all hosts unchanged — ``jax.devices()``
becomes the global device set, XLA routes the Sinkhorn ``psum``/``pmax``
collectives over ICI within a slice and DCN across slices, and no solver
code differs between 1 and N hosts. (This replaces what NCCL/MPI init +
communicator plumbing does for the reference stack's GPU cousins.)

Per-host data feeding: each host holds only its own objects (its servers'
directory shard). :func:`distributed_array` assembles the global sharded
array from per-host shards without ever materializing the global array on
any one host — the multi-host analog of ``jax.device_put``.

Bring-up recipe (one process per host, e.g. under a process manager or the
TPU pod runtime):

    from rio_tpu.parallel import make_mesh, multihost

    multihost.initialize()          # env-driven on TPU pods; explicit
                                    # coordinator args elsewhere
    mesh = make_mesh()              # spans ALL hosts' devices
    obj_feat = multihost.distributed_array(
        mesh, P("obj", None), local_obj_feat)   # this host's rows only
    res = sharded_hierarchical_assign(mesh, obj_feat, ...)

Single-process (tests, one chip, CPU mesh) every function degrades to the
local equivalent, so the same program text runs everywhere.
"""

from __future__ import annotations

import logging

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("rio_tpu.parallel.multihost")

__all__ = ["initialize", "is_multihost", "distributed_array", "process_rows"]


def _already_initialized() -> bool:
    """Whether jax.distributed.initialize has run, WITHOUT initializing
    the backend (the public probes all do)."""
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client is not None
    except Exception:  # internal layout changed; assume not initialized
        return False


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_ids=None,
) -> bool:
    """Idempotent :func:`jax.distributed.initialize` wrapper.

    With no arguments, jax reads the cluster environment (TPU pod runtime,
    SLURM, etc.); pass explicit coordinator args everywhere else. Safe to
    call unconditionally at server startup:

    * already initialized -> no-op;
    * single-process with no cluster env and no args -> no-op (jax would
      otherwise raise on the missing coordinator);
    * returns True iff the runtime is multi-process afterwards.

    NOTE this function must not touch the jax backend before calling
    ``jax.distributed.initialize`` — even ``jax.process_count()``
    initializes the single-process backend and silently breaks the
    multi-controller bring-up — hence the internal-state probe.
    """
    if _already_initialized():
        return jax.process_count() > 1
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        )
    except ValueError as e:
        # jax runs its cluster auto-detection inside initialize(); with no
        # explicit coordinator and no recognizable cluster it raises this
        # — which IS the single-process answer, not an error. (Env-var
        # sniffing is not a substitute: a one-host image may well export
        # TPU_WORKER_HOSTNAMES=localhost without any cluster.)
        if (
            coordinator_address is None
            and num_processes is None
            and process_id is None
            and "coordinator_address" in str(e)
        ):
            log.debug("no cluster detected; staying single-process")
            return False
        # Any explicit multi-process intent (world size / rank given but
        # the coordinator missing) must fail loudly, not downgrade.
        raise
    except RuntimeError as e:
        msg = str(e).lower()
        if "already" in msg:
            pass  # double-initialize (e.g. two Servers in one process)
        elif "before" in msg and coordinator_address is None:
            # The backend is already up (long-lived process, test runner):
            # opportunistic env-driven bring-up is no longer possible —
            # stay in whatever mode the process is in. With an EXPLICIT
            # coordinator this is a real ordering bug and still raises.
            # On what LOOKS like a cluster, a silent downgrade to N
            # independent single-host programs would be invisible in
            # production — warn loudly there. (Env sniffing is fine for
            # log-level selection; a false negative only softens the log.)
            import os

            clusterish = any(
                os.environ.get(k)
                for k in (
                    "JAX_COORDINATOR_ADDRESS",
                    "COORDINATOR_ADDRESS",
                    "MEGASCALE_COORDINATOR_ADDRESS",
                    "SLURM_JOB_ID",
                )
            )
            (log.warning if clusterish else log.debug)(
                "jax backend was initialized before multihost.initialize();"
                " staying single-process. For multi-host, call initialize()"
                " before ANY jax backend use (jax.devices, computations)."
            )
            return jax.process_count() > 1
        else:
            raise
    return jax.process_count() > 1


def is_multihost() -> bool:
    """True iff this process is part of a multi-controller runtime.

    Safe to call before :func:`initialize`: probes the distributed state
    WITHOUT touching the jax backend (``jax.process_count()`` would boot
    the single-process backend and break a later bring-up).
    """
    if not _already_initialized():
        return False
    return jax.process_count() > 1


def process_rows(
    n_global: int, mesh: Mesh, axis: str | tuple[str, ...] | None = None
) -> slice:
    """The global row range this PROCESS must supply for a row-sharded
    array of ``n_global`` rows.

    ``axis`` must name the mesh axes the ROW dimension is sharded over,
    exactly as in the ``PartitionSpec`` fed to :func:`distributed_array` —
    the default (``None``) means ALL mesh axes in order, matching the
    ``P(mesh.axis_names, None)`` layout the sharded solvers use. Rows are
    laid out in mesh-axis order, the same order
    :func:`distributed_array` assembles them.
    """
    import numpy as np

    if axis is None:
        axes = tuple(mesh.axis_names)
    elif isinstance(axis, str):
        axes = (axis,)
    else:
        axes = tuple(axis)
    sizes = [mesh.shape[a] for a in axes]
    n_shards = int(np.prod(sizes))
    per_shard, rem = divmod(n_global, n_shards)
    assert rem == 0, (n_global, n_shards)
    # Which row-shard indices live on this process's devices? A device at
    # grid position idx owns row shard ravel(idx restricted to `axes`).
    names = list(mesh.axis_names)
    axis_pos = [names.index(a) for a in axes]
    local = set()
    dev_grid = np.asarray(mesh.devices)
    for idx in np.ndindex(dev_grid.shape):
        if dev_grid[idx].process_index == jax.process_index():
            coords = tuple(idx[p] for p in axis_pos)
            local.add(int(np.ravel_multi_index(coords, sizes)))
    if not local:
        raise ValueError(
            f"process {jax.process_index()} owns no devices in this mesh "
            f"({dict(mesh.shape)}); build the mesh over devices from every "
            f"participating process"
        )
    lo, hi = min(local), max(local)
    assert local == set(range(lo, hi + 1)), "non-contiguous process shards"
    return slice(lo * per_shard, (hi + 1) * per_shard)


def distributed_array(mesh: Mesh, spec: P, local_data) -> jax.Array:
    """Assemble a globally-sharded array from per-process local shards.

    ``local_data`` is this process's slice (see :func:`process_rows`);
    no host ever materializes the global array. Single-process this is
    exactly ``jax.device_put(local_data, NamedSharding(mesh, spec))``.
    """
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(local_data, sharding)
    return jax.make_array_from_process_local_data(sharding, local_data)
