"""Multi-chip sharded placement solve: mesh + shard_map + XLA collectives.

The scale target (``BASELINE.md`` row 5) is 10M objects x 1k nodes — a cost
matrix that must be sharded across chips. The design follows the standard
TPU recipe: pick a 2-D ``jax.sharding.Mesh`` with axes ``("obj", "node")``,
shard the cost matrix on both axes, express the Sinkhorn row/column
normalizations with explicit ``psum``/``pmax`` collectives inside
``shard_map`` (they ride ICI within a slice), and let XLA lay out everything
else. The reference has no device story at all — its cross-node transport is
tokio TCP + SQL rendezvous (``rio-rs/src/service.rs:370-378``); here the
control plane stays on host TCP while the solver plane lives on the mesh.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

__all__ = [
    "HierarchicalResult",
    "hierarchical_assign",
    "make_mesh",
    "shard_cost",
    "sharded_hierarchical_assign",
    "sharded_scaling_sinkhorn",
    "sharded_sinkhorn",
    "sharded_sinkhorn_assign",
]


def __getattr__(name):
    # Lazy: hierarchical pulls in the ops stack; keep `import rio_tpu.parallel`
    # light for users who only need the mesh helpers.
    if name in ("HierarchicalResult", "hierarchical_assign", "sharded_hierarchical_assign"):
        from . import hierarchical

        return getattr(hierarchical, name)
    if name == "multihost":
        # importlib, not `from . import`: the from-import re-enters this
        # __getattr__ while the attribute is still unset (RecursionError).
        import importlib

        return importlib.import_module(".multihost", __name__)
    raise AttributeError(name)


def make_mesh(devices=None, *, obj_axis: int | None = None) -> Mesh:
    """Build a 2-D ``("obj", "node")`` mesh over the given (or all) devices.

    The object axis gets the larger factor — the object count dominates the
    node count by ~4 orders of magnitude (10M x 1k), so row sharding carries
    almost all the memory.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if obj_axis is None:
        obj_axis = n
        node_axis = 1
        # Prefer a 2-D factorization when n is not prime, e.g. 8 -> (4, 2).
        for cand in range(int(math.isqrt(n)), 0, -1):
            if n % cand == 0:
                obj_axis, node_axis = n // cand, cand
                break
    else:
        node_axis = n // obj_axis
    import numpy as np

    return Mesh(np.asarray(devices).reshape(obj_axis, node_axis), ("obj", "node"))


def shard_cost(mesh: Mesh, cost: jax.Array) -> jax.Array:
    """Place a cost matrix on the mesh, rows over "obj", cols over "node"."""
    return jax.device_put(cost, NamedSharding(mesh, P("obj", "node")))


def _dist_lse(z_local: jax.Array, axis: int, mesh_axis: str) -> jax.Array:
    """Numerically stable log-sum-exp over a sharded axis.

    Local LSE along ``axis``, then the standard two-collective combine:
    global max via ``pmax`` and a ``psum`` of re-based exponentials over the
    mesh axis. Both collectives are single-hop ICI reductions.
    """
    local_max = jnp.max(z_local, axis=axis)
    gmax = lax.pmax(local_max, mesh_axis)
    safe = jnp.where(jnp.isfinite(gmax), gmax, 0.0)
    local_sum = jnp.sum(jnp.exp(z_local - jnp.expand_dims(safe, axis)), axis=axis)
    gsum = lax.psum(local_sum, mesh_axis)
    return safe + jnp.log(jnp.maximum(gsum, 1e-30))


def sharded_sinkhorn(
    mesh: Mesh,
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
) -> tuple[jax.Array, jax.Array]:
    """Log-domain Sinkhorn with the cost matrix sharded on both mesh axes.

    Returns (f, g) potentials, sharded P("obj") / P("node") respectively.
    Semantics match :func:`rio_tpu.ops.sinkhorn.sinkhorn`; see there for the
    math. Row updates reduce over the "node" axis, column updates over the
    "obj" axis — each iteration is two ICI reductions per direction.
    """

    def solve(c, a, b):
        c = c.astype(jnp.float32)
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        total_a = jnp.maximum(lax.psum(jnp.sum(a), "obj"), 1e-30)
        total_b = jnp.maximum(lax.psum(jnp.sum(b), "node"), 1e-30)
        a = a / total_a
        b = b / total_b
        log_a = jnp.where(a > 0, jnp.log(jnp.maximum(a, 1e-30)), -jnp.inf)
        log_b = jnp.where(b > 0, jnp.log(jnp.maximum(b, 1e-30)), -jnp.inf)

        def body(carry, _):
            f, g = carry
            f = eps * (log_a - _dist_lse((g[None, :] - c) / eps, 1, "node"))
            f = jnp.where(jnp.isfinite(log_a), f, -jnp.inf)
            g = eps * (log_b - _dist_lse((f[:, None] - c) / eps, 0, "obj"))
            g = jnp.where(jnp.isfinite(log_b), g, -jnp.inf)
            return (f, g), None

        # Mark the carry as varying over its mesh axis up front (JAX >= 0.9
        # shard_map tracks manual-axis variance through scan).
        f0 = lax.pcast(jnp.zeros(c.shape[0], jnp.float32), ("obj",), to="varying")
        g0 = lax.pcast(jnp.zeros(c.shape[1], jnp.float32), ("node",), to="varying")
        (f, g), _ = lax.scan(body, (f0, g0), None, length=n_iters)
        return f, g

    fn = shard_map(
        solve,
        mesh=mesh,
        in_specs=(P("obj", "node"), P("obj"), P("node")),
        out_specs=(P("obj"), P("node")),
    )
    return fn(cost, row_mass, col_capacity)


def sharded_scaling_sinkhorn(
    mesh: Mesh,
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    kernel_dtype=jnp.bfloat16,
) -> tuple[jax.Array, jax.Array]:
    """Scaling-form Sinkhorn-Knopp sharded over the 2-D mesh.

    The kernel ``K = exp(-C/eps)`` is built shard-local from the sharded
    cost (one transcendental sweep total); each iteration is two local
    matvec partials + one ``psum`` per direction — no per-iteration
    transcendentals, matching :func:`rio_tpu.ops.scaling.scaling_sinkhorn`
    semantics (returns log-domain potentials (f, g)).
    """

    def solve(c, a, b):
        c = c.astype(jnp.float32)
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        a = a / jnp.maximum(lax.psum(jnp.sum(a), "obj"), 1e-30)
        b = b / jnp.maximum(lax.psum(jnp.sum(b), "node"), 1e-30)
        # PER-ROW gauge shift (pmin across node shards): every row keeps its
        # best entry at exp(0)=1, so no row underflows to all-zeros however
        # wide the cost range — same stabilization as scaling_core (a global
        # shift breaks tail rows once range/eps >> 88); see ops/scaling.py.
        shift = lax.pmin(jnp.min(c, axis=1, keepdims=True), "node")
        shift = jnp.where(jnp.isfinite(shift), shift, 0.0)
        K = jnp.exp(-(c - shift) / eps).astype(kernel_dtype)

        def body(carry, _):
            u, v = carry
            Kv = lax.psum(
                jnp.matmul(K, v.astype(kernel_dtype), preferred_element_type=jnp.float32),
                "node",
            )
            u = jnp.where(a > 0, a / jnp.maximum(Kv, 1e-30), 0.0)
            KTu = lax.psum(
                jnp.matmul(u.astype(kernel_dtype), K, preferred_element_type=jnp.float32),
                "obj",
            )
            v = jnp.where(b > 0, b / jnp.maximum(KTu, 1e-30), 0.0)
            return (u, v), None

        u0 = lax.pcast(jnp.zeros(c.shape[0], jnp.float32), ("obj",), to="varying")
        v0 = lax.pcast(jnp.ones(c.shape[1], jnp.float32), ("node",), to="varying")
        (u, v), _ = lax.scan(body, (u0, v0), None, length=n_iters)
        f = jnp.where(
            u > 0, eps * jnp.log(jnp.maximum(u, 1e-30)) + shift[:, 0], -jnp.inf
        )
        g = jnp.where(v > 0, eps * jnp.log(jnp.maximum(v, 1e-30)), -jnp.inf)
        return f, g

    fn = shard_map(
        solve,
        mesh=mesh,
        in_specs=(P("obj", "node"), P("obj"), P("node")),
        out_specs=(P("obj"), P("node")),
    )
    return fn(cost, row_mass, col_capacity)


@jax.jit
def _assign_with_g(cost, g):
    g = jnp.where(jnp.isfinite(g), g, -jnp.inf)
    return jnp.argmin(cost.astype(jnp.float32) - g[None, :], axis=1).astype(jnp.int32)


def sharded_sinkhorn_assign(
    mesh: Mesh,
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
) -> jax.Array:
    """Sharded solve + assignment extraction.

    The extraction (``argmin_j cost - g``) runs under plain jit with the cost
    still sharded P("obj", "node"): XLA all-gathers the small ``g`` vector
    along "node" and reduces — no hand-written collective needed.
    """
    f, g = sharded_sinkhorn(
        mesh, cost, row_mass, col_capacity, eps=eps, n_iters=n_iters
    )
    return _assign_with_g(cost, g)
