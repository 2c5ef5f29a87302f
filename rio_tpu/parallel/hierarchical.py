"""Two-level (hierarchical) optimal-transport placement.

The 1k-node x 10M-object tier (``BASELINE.md`` row 5) cannot materialize a
flat cost matrix: 10M x 1k fp32 is 40 GB, over a single chip's HBM. The
hierarchical solve replaces it with two bounded stages over a *factorized*
affinity (object features x node features, the MXU-friendly form):

1. **Coarse**: nodes are partitioned into ``G`` groups (racks/hosts or
   contiguous slices); a group's affinity for an object is that of its
   best live member (a capacity-weighted mean dilutes one warm node by
   1/S) and its capacity the sum of its live members'. One (N x G)
   Sinkhorn solve + capacity-aware rounding assigns every object a group,
   with per-group quotas following group capacity.
2. **Fine**: objects are bucketed by group (static bucket size with slack,
   scatter by rank-in-group), and ``G`` independent (B x S) solves run
   batched under ``vmap`` — batched matmuls and batched Sinkhorn, ideal
   XLA shapes. Results map back through the group member table.

Peak memory is O(N*G + N*S + N*d) instead of O(N*M) — for 10M x 1024
with G = S = 32 that is ~2.6 GB instead of 40 GB.

Scaling out: the object axis is embarrassingly parallel — shard objects
across the mesh and give every shard ``1/n_shards`` of each node's
capacity (:func:`sharded_hierarchical_assign`); no cross-shard collective
is needed beyond the initial capacity split, so the solve rides data
parallelism to any mesh size. Past the per-shard compile wall, the same
independence composes with temporal chunking
(:func:`mesh_chunked_hierarchical_assign`): each (device, chunk) cell
solves its slice against ``1/(n_shards*n_chunks)`` capacity, so ONE
compiled body at the cell shape covers 10M-100M rows.

The reference has no counterpart — its placement directory is row-by-row
SQL (``rio-rs/src/object_placement/sqlite.rs:68-100``) with a random-pick
policy (``client/mod.rs:255-262``); this module is the scale ceiling of
the TPU-native redesign.
"""

from __future__ import annotations

import functools
from importlib import import_module
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The three solve steps are reached through their modules when a body is
# TRACED, not bound here at import: whoever replaces one in its module (the
# benchmark's bfloat16 control does) then lowers this route as it lowers the
# flat ones. (By module path: ``rio_tpu.ops`` re-exports a function under
# each module's name.)
from ..ops.sinkhorn import route_sentinel_spill
from ..tracing import stage

_scaling = import_module("..ops.scaling", __package__)
_sinkhorn = import_module("..ops.sinkhorn", __package__)

__all__ = [
    "HierarchicalResult",
    "chunked_hierarchical_assign",
    "chunked_hierarchical_assign_timed",
    "hierarchical_assign",
    "mesh_chunked_hierarchical_assign",
    "mesh_chunked_hierarchical_assign_timed",
    "sharded_hierarchical_assign",
]


class HierarchicalResult(NamedTuple):
    assignment: jax.Array  # (N,) int32 global node index
    group: jax.Array       # (N,) int32 coarse group index
    overflow: jax.Array    # scalar int32: objects that missed their bucket
    # (G,) coarse-stage group potentials — the warm seed for the NEXT
    # (delta) solve's coarse stage. None on the sharded path (each shard
    # solves its own coarse problem; no single seed to return).
    coarse_g: jax.Array | None = None
    # Scalar final L1 column-marginal violation of the coarse solve —
    # the convergence residual SolveStats surfaces. None on the sharded
    # path (per-shard residuals have no single summary without a
    # collective this solve otherwise never needs).
    coarse_err: jax.Array | None = None


_HIER_STATIC = ("n_groups", "bucket", "eps", "coarse_iters", "fine_iters")

# The affinities (features x node embeddings, a contraction over d = 16) are
# float32 products on every backend. A TPU's default would round both operands
# to bfloat16 first, and a cost that is then divided by eps = 0.05 carries that
# into the plan: 1.0e-2 in a coarse potential on four v5e chips against 7e-4
# in float32 (PERF.md, PR 34), more than a member more or less in a group
# moves one. Six bfloat16 passes of a 16-deep contraction cost nothing beside
# the thirty-iteration solves that follow.
_AFFINITY_PRECISION = jax.lax.Precision.HIGHEST


def _hierarchical_assign_impl(
    obj_feat: jax.Array,
    node_feat: jax.Array,
    node_capacity: jax.Array,
    alive: jax.Array,
    *,
    n_groups: int,
    bucket: int | None = None,
    eps: float = 0.05,
    coarse_iters: int = 30,
    fine_iters: int = 30,
    coarse_g_init: jax.Array | None = None,
) -> HierarchicalResult:
    """Two-level OT assignment over factorized affinity.

    Args:
      obj_feat: (N, d) object features (e.g. hashed identity embeddings).
      node_feat: (d, M) node features; affinity[i, j] = obj_feat[i] @ node_feat[:, j].
      node_capacity: (M,) capacity per node (0 = retired slot).
      alive: (M,) liveness in {0.0, 1.0}; dead nodes attract nothing.
      n_groups: number of node groups; M must be divisible by it.
      bucket: per-group object bucket size (static). Defaults to
        ``ceil(1.25 * N / G)`` rounded up to a multiple of 8 — sized for
        roughly uniform group capacity. With skewed capacity (or mostly-dead
        groups) pass an explicit bucket ~ ``1.3 * N * max_group_cap_share``
        or quotas overflow into the fallback path.
      coarse_g_init: optional (G,) warm-start potentials for the coarse
        solve — the previous solve's ``coarse_g``, fed back by the delta
        rebalance path so a churn re-solve's coarse stage converges in a
        handful of iterations. The fine stages always start cold (their
        populations change with the coarse outcome).
    """
    n, d = obj_feat.shape
    d2, m = node_feat.shape
    assert d == d2 and m % n_groups == 0, (obj_feat.shape, node_feat.shape, n_groups)
    s = m // n_groups
    if bucket is None:
        bucket = -(-int(1.25 * n) // n_groups)
        bucket = -(-bucket // 8) * 8
    obj_feat = obj_feat.astype(jnp.float32)
    node_feat = node_feat.astype(jnp.float32)
    cap = node_capacity.astype(jnp.float32) * alive.astype(jnp.float32)

    # ---- stage 1: coarse obj -> group ------------------------------------
    # Coarse affinity = the object's BEST live member in each group, not
    # the group's mean embedding: with near-orthogonal node embeddings a
    # mean dilutes a single warm node by 1/S (measured: it dropped the
    # churn-failover locality hit rate to chance in
    # tests/test_affinity_payoff.py), while the max routes the object to
    # whichever group holds its warm state.  Computed blockwise over
    # groups — an (N, S) temp per step, the same working-set scale as the
    # fine stage; the (N, M) product is never materialized.
    node_feat_grouped = node_feat.reshape(d, n_groups, s).transpose(1, 0, 2)
    alive_grouped = (cap > 0).reshape(n_groups, s)
    group_cap = cap.reshape(n_groups, s).sum(axis=1)  # (G,)

    def _group_best(args):
        nf_g, alive_g = args  # (d, S), (S,)
        scores = jnp.matmul(obj_feat, nf_g, precision=_AFFINITY_PRECISION)  # (N, S)
        scores = jnp.where(alive_g[None, :], scores, -jnp.inf)
        return jnp.max(scores, axis=1)  # (N,)

    coarse_aff = jax.lax.map(_group_best, (node_feat_grouped, alive_grouped))
    live_group = group_cap > 0  # (G,)
    raw_cost = -coarse_aff.T  # (N, G); +inf on all-dead groups
    # Normalize the cost scale so eps is a relative knob (and the scaling
    # solver's exp(-C/eps) stays in float range for any feature magnitude)
    # — statistics over LIVE groups only, then a finite terrible cost on
    # dead groups (their zero group_cap already excludes them from the OT
    # marginals).
    std = jnp.std(raw_cost, where=live_group[None, :])
    coarse_cost = jnp.where(
        live_group[None, :], raw_cost / jnp.maximum(std, 1e-6), 1e6
    )
    mass = jnp.ones((n,), jnp.float32)
    res_c = _scaling.scaling_sinkhorn(
        coarse_cost, mass, group_cap, eps=eps, n_iters=coarse_iters,
        g_init=coarse_g_init,
    )
    group = _sinkhorn.plan_rounded_assign(coarse_cost, res_c.f, res_c.g, eps)  # (N,)
    # Exact group quotas: CDF rounding matches group capacities only in
    # expectation; the repair pins every group to its largest-remainder
    # quota, so a bucket sized >= max quota makes overflow structurally
    # impossible (instead of merely improbable).
    group = _sinkhorn.exact_quota_repair(
        group, group_cap / jnp.maximum(jnp.sum(group_cap), 1e-30) * n
    )

    # ---- bucket objects by group (static shapes) -------------------------
    # rank-in-group via a stable sort by group id; each group's objects are
    # a contiguous run of the sorted order.
    order = jnp.argsort(group, stable=True)  # (N,)
    sorted_group = group[order]
    counts = jnp.bincount(group, length=n_groups)  # (G,)
    starts = jnp.cumsum(counts) - counts  # (G,)
    rank = jnp.arange(n) - starts[sorted_group]  # rank within group
    in_bucket = rank < bucket
    overflow = jnp.sum(~in_bucket).astype(jnp.int32)
    # Scatter sorted object indices into the (G, bucket) table; sentinel N
    # marks padding (reads a zero feature row). Overflow writes are routed
    # to an out-of-bounds slot and dropped.
    flat = jnp.full((n_groups * bucket,), n, jnp.int32)
    slot = jnp.where(in_bucket, sorted_group * bucket + rank, n_groups * bucket)
    flat = flat.at[slot].set(order.astype(jnp.int32), mode="drop")
    idx = flat.reshape(n_groups, bucket)  # (G, B) object ids or N

    # ---- stage 2: fine per-group solves, batched -------------------------
    obj_feat_pad = jnp.concatenate([obj_feat, jnp.zeros((1, d), jnp.float32)], 0)
    feat_b = obj_feat_pad[idx]  # (G, B, d)
    node_feat_g = node_feat.reshape(d, n_groups, s).transpose(1, 0, 2)  # (G, d, S)
    fine_cost = -jnp.einsum(
        "gbd,gds->gbs", feat_b, node_feat_g, precision=_AFFINITY_PRECISION
    )  # (G, B, S)
    fine_cost = fine_cost / jnp.maximum(jnp.std(fine_cost), 1e-6)
    fine_mass = (idx < n).astype(jnp.float32)  # (G, B)
    cap_g = cap.reshape(n_groups, s)  # (G, S)

    def solve_one(c, a, b):
        r = _scaling.scaling_sinkhorn(c, a, b, eps=eps, n_iters=fine_iters)
        local = _sinkhorn.plan_rounded_assign(c, r.f, r.g, eps)
        # Exact per-node quotas within the group (same largest-remainder
        # repair as the coarse stage): padding rows go to a sentinel slot
        # sized to their count, so real rows land exactly on capacity
        # shares of the group's real population.
        n_real = jnp.sum(a)
        local = jnp.where(a > 0, local, s)
        pad_count = (jnp.float32(a.shape[0]) - n_real)[None]
        expected = jnp.concatenate(
            [b / jnp.maximum(jnp.sum(b), 1e-30) * n_real, pad_count]
        )
        repaired = _sinkhorn.exact_quota_repair(local, expected)
        # Real rows spilled onto the sentinel column (quota drift / refill
        # clip) would be take_along_axis-clamped onto member s-1, which may
        # be dead — route them to the group's best live member instead.
        return route_sentinel_spill(repaired, a > 0, s, b)

    fine_local = jax.vmap(solve_one)(fine_cost, fine_mass, cap_g)  # (G, B) in [0,S]
    members = jnp.arange(m, dtype=jnp.int32).reshape(n_groups, s)
    fine_global = jnp.take_along_axis(members, fine_local, axis=1)  # (G, B)

    # ---- map back to object order ----------------------------------------
    assignment = jnp.zeros((n,), jnp.int32)
    assignment = assignment.at[idx.reshape(-1)].set(
        fine_global.reshape(-1), mode="drop"
    )
    # Overflow objects (rank >= bucket) fall back to their group's highest-
    # capacity live member (rare: bucket has 25% slack over a capacity-
    # balanced coarse quota; never materializes an (N x M) matrix).
    fallback = jnp.take_along_axis(
        members, jnp.argmax(cap_g, axis=1, keepdims=True), axis=1
    )[:, 0]  # (G,)
    missed = jnp.zeros((n,), bool).at[order].set(~in_bucket)
    assignment = jnp.where(missed, fallback[group], assignment)
    return HierarchicalResult(
        assignment=assignment, group=group, overflow=overflow,
        coarse_g=res_c.g, coarse_err=res_c.err,
    )


hierarchical_assign = jax.jit(_hierarchical_assign_impl, static_argnames=_HIER_STATIC)

@functools.partial(jax.jit, static_argnames=("n_groups", "n_chunks", "bucket", "eps", "coarse_iters", "fine_iters"))
def chunked_hierarchical_assign(
    obj_feat: jax.Array,
    node_feat: jax.Array,
    node_capacity: jax.Array,
    alive: jax.Array,
    *,
    n_groups: int,
    n_chunks: int,
    coarse_g_init: jax.Array | None = None,
    **kw,
) -> HierarchicalResult:
    """Single-chip scale-out: the sharded solve's design, run temporally.

    The TPU backend's compile time for :func:`hierarchical_assign` is
    superlinear in the object count (r5 capture on v5e, not re-measured:
    50 s at 655k, 599 s at 2.6M — while CPU XLA stays flat at ~7 s), so
    giant flat shapes price a full re-solve out of reach. This wrapper
    reuses the exact per-shard independence `sharded_hierarchical_assign`
    rides (each shard solves its slice against ``1/n_shards`` of every
    node's capacity; marginal normalization spreads each slice across the
    same capacity proportions): chunks run *sequentially* under
    ``lax.map``, so XLA traces and compiles ONE body at the chunk shape —
    compile cost is pinned to the chunk size while execution scales
    linearly with N. Per-chunk exact quota repair makes total node loads
    exact to chunk granularity, same as the mesh version.
    """
    n = obj_feat.shape[0]
    assert n % n_chunks == 0, (n, n_chunks)
    of = obj_feat.reshape(n_chunks, n // n_chunks, obj_feat.shape[1])

    def one(of_c):
        return hierarchical_assign(
            of_c, node_feat, node_capacity / n_chunks, alive,
            n_groups=n_groups, coarse_g_init=coarse_g_init, **kw,
        )

    res = jax.lax.map(one, of)
    return HierarchicalResult(
        assignment=res.assignment.reshape(-1),
        group=res.group.reshape(-1),
        overflow=jnp.sum(res.overflow),
        # Every chunk solves the same capacity proportions (its slice vs
        # 1/n_chunks of each node), so any chunk's coarse potentials are a
        # valid warm seed for the next solve; keep the last.
        coarse_g=res.coarse_g[-1],
        coarse_err=res.coarse_err[-1],
    )


def chunked_hierarchical_assign_timed(
    obj_feat: jax.Array,
    node_feat: jax.Array,
    node_capacity: jax.Array,
    alive: jax.Array,
    *,
    n_groups: int,
    n_chunks: int,
    coarse_g_init: jax.Array | None = None,
    **kw,
) -> tuple[HierarchicalResult, list[float]]:
    """:func:`chunked_hierarchical_assign` with per-chunk host timings.

    The ``lax.map`` form runs every chunk inside ONE executable, so chunk
    boundaries are invisible to the host; this twin loops the chunks on
    the host instead, calling the SAME jitted :func:`hierarchical_assign`
    per chunk (compile stays pinned to the chunk shape — the whole point
    of chunking) and timing each dispatch+``block_until_ready`` cycle.
    Identical inputs per chunk, so outputs match the ``lax.map`` form
    exactly (``tests/test_hierarchical.py`` pins the parity); the first
    chunk's timing includes the one-time compile, which is exactly the
    compile-vs-execute signal SolveStats wants. The sync per chunk is a
    single ``block_until_ready`` on the jit result, never a value pull.

    Returns ``(result, chunk_ms)`` with one wall-ms entry per chunk.
    """
    import time as _time

    n = obj_feat.shape[0]
    assert n % n_chunks == 0, (n, n_chunks)
    of = jnp.asarray(obj_feat).reshape(n_chunks, n // n_chunks, obj_feat.shape[1])
    # Sync staged inputs BEFORE the timed loop: dispatch is async, so a
    # still-pending producer chain (e.g. feature generation, O(N) in total
    # rows) would otherwise drain inside chunk 0's timer and masquerade as
    # compile time — chunk_ms must measure the solve, pinned to cell shape.
    jax.block_until_ready((of, node_feat, node_capacity, alive))
    assignments: list[jax.Array] = []
    groups: list[jax.Array] = []
    overflow = jnp.zeros((), jnp.int32)
    chunk_ms: list[float] = []
    res = None
    for c in range(n_chunks):
        t0 = _time.perf_counter()
        res = hierarchical_assign(
            of[c], node_feat, node_capacity / n_chunks, alive,
            n_groups=n_groups, coarse_g_init=coarse_g_init, **kw,
        )
        jax.block_until_ready(res.assignment)
        chunk_ms.append(round((_time.perf_counter() - t0) * 1e3, 3))
        assignments.append(res.assignment)
        groups.append(res.group)
        overflow = overflow + res.overflow
    return (
        HierarchicalResult(
            assignment=jnp.concatenate(assignments),
            group=jnp.concatenate(groups),
            overflow=overflow,
            coarse_g=res.coarse_g,
            coarse_err=res.coarse_err,
        ),
        chunk_ms,
    )


def _mesh_inputs(
    mesh, obj_feat, node_feat, node_capacity, alive, coarse_g_init, n_groups
):
    """Place the solve inputs: object rows sharded, everything else replicated.

    A missing warm seed becomes the zero seed — bitwise the same solve
    (``v0 = exp(0) = 1`` either way, see ``ops.scaling.scaling_core``) —
    and an always-an-array seed keeps the traced signature stable instead
    of minting a second executable on the cold/warm flip.
    """
    axes = mesh.axis_names
    obj_feat = jax.device_put(obj_feat, NamedSharding(mesh, P(axes, None)))
    rep = NamedSharding(mesh, P())
    node_feat = jax.device_put(jnp.asarray(node_feat), rep)
    node_capacity = jax.device_put(jnp.asarray(node_capacity), rep)
    alive = jax.device_put(jnp.asarray(alive), rep)
    if coarse_g_init is None:
        coarse_g_init = jnp.zeros((n_groups,), jnp.float32)
    coarse_g_init = jax.device_put(jnp.asarray(coarse_g_init, jnp.float32), rep)
    return obj_feat, node_feat, node_capacity, alive, coarse_g_init


def _hier_out_specs(axes):
    return HierarchicalResult(
        assignment=P(axes), group=P(axes), overflow=P(),
        # Coarse potentials/residual come back REPLICATED: every shard
        # solves the same capacity proportions (its slice vs 1/n_shards of
        # each node), so the pmean of the per-shard potentials is a valid
        # warm seed for the next solve — this is what persists into
        # PlanState on the mesh path (it used to be dropped entirely).
        coarse_g=P(), coarse_err=P(),
    )


def sharded_hierarchical_assign(
    mesh: Mesh,
    obj_feat: jax.Array,
    node_feat: jax.Array,
    node_capacity: jax.Array,
    alive: jax.Array,
    *,
    n_groups: int,
    coarse_g_init: jax.Array | None = None,
    **kw,
) -> HierarchicalResult:
    """Data-parallel hierarchical solve: objects sharded over the mesh.

    ``shard_map`` runs an *independent* two-level solve per object shard
    (marginal normalization makes each shard spread its slice across the
    same capacity proportions), so no cross-shard collective is needed at
    all — the sort/bucket/scatter machinery stays shard-local instead of
    turning into a global all-to-all. Node-side inputs are replicated
    (O(M), tiny next to the object axis); the overflow counter is psum'd
    and the coarse potentials/residual are pmean'd to a replicated warm
    seed (``coarse_g_init`` threads the previous one back in).
    """
    axes = mesh.axis_names
    obj_feat, node_feat, node_capacity, alive, coarse_g_init = _mesh_inputs(
        mesh, obj_feat, node_feat, node_capacity, alive, coarse_g_init, n_groups
    )

    def local_solve(of, nf, cap, al, g0):
        res = hierarchical_assign(
            of, nf, cap, al, n_groups=n_groups, coarse_g_init=g0, **kw
        )
        return HierarchicalResult(
            assignment=res.assignment,
            group=res.group,
            overflow=jax.lax.psum(res.overflow, axes),
            coarse_g=jax.lax.pmean(res.coarse_g, axes),
            coarse_err=jax.lax.pmean(res.coarse_err, axes),
        )

    fn = shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(P(axes, None), P(), P(), P(), P()),
        out_specs=_hier_out_specs(axes),
        check_vma=False,
    )
    return fn(obj_feat, node_feat, node_capacity, alive, coarse_g_init)


def mesh_chunked_hierarchical_assign(
    mesh: Mesh,
    obj_feat: jax.Array,
    node_feat: jax.Array,
    node_capacity: jax.Array,
    alive: jax.Array,
    *,
    n_groups: int,
    n_chunks: int,
    coarse_g_init: jax.Array | None = None,
    **kw,
) -> HierarchicalResult:
    """Mesh x chunk composed solve: devices AND chunks scale the row count.

    :func:`sharded_hierarchical_assign` divides N by the device count but
    still compiles one flat body per shard — at TPU-backend compile costs
    superlinear in the row count (CLAUDE.md) that hits the same wall
    one octave later. This composition runs the ``lax.map``-chunked body
    *inside* each shard: every (device, chunk) cell solves
    ``N / (n_shards * n_chunks)`` rows against ``1 / (n_shards *
    n_chunks)`` of each node's capacity (the same per-slice independence
    both parents ride), so the ONE compiled body is pinned to the cell
    shape while rows scale with devices times chunks. Overflow is psum'd;
    coarse potentials are pmean'd across shards (last chunk per shard,
    matching :func:`chunked_hierarchical_assign`) into a replicated warm
    seed.
    """
    axes = mesh.axis_names
    n_shards = int(mesh.devices.size)
    n = obj_feat.shape[0]
    assert n % (n_shards * n_chunks) == 0, (n, n_shards, n_chunks)
    scale = n_shards * n_chunks
    obj_feat, node_feat, node_capacity, alive, coarse_g_init = _mesh_inputs(
        mesh, obj_feat, node_feat, node_capacity, alive, coarse_g_init, n_groups
    )

    def local_solve(of, nf, cap, al, g0):
        ofc = of.reshape(n_chunks, of.shape[0] // n_chunks, of.shape[1])

        def one(of_c):
            # Divide by the FULL scale in one step — the timed twin does
            # the identical division, so the two forms stay comparable to
            # the last ulp.
            return hierarchical_assign(
                of_c, nf, cap / scale, al,
                n_groups=n_groups, coarse_g_init=g0, **kw,
            )

        res = jax.lax.map(one, ofc)
        return HierarchicalResult(
            assignment=res.assignment.reshape(-1),
            group=res.group.reshape(-1),
            overflow=jax.lax.psum(jnp.sum(res.overflow), axes),
            coarse_g=jax.lax.pmean(res.coarse_g[-1], axes),
            coarse_err=jax.lax.pmean(res.coarse_err[-1], axes),
        )

    fn = shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(P(axes, None), P(), P(), P(), P()),
        out_specs=_hier_out_specs(axes),
        check_vma=False,
    )
    return fn(obj_feat, node_feat, node_capacity, alive, coarse_g_init)


@functools.lru_cache(maxsize=8)
def _mesh_cell_solver(mesh: Mesh, scale: int, n_groups: int, kw_key: tuple):
    """One jitted shard_map cell solver per (mesh, scale, solve config).

    The timed twin dispatches every chunk through this SAME executable —
    the cache (keyed on hashables only; ``Mesh`` hashes by device/axis
    layout) is what pins compile cost to the first chunk of the first
    solve at a given cell shape, across chunks AND across rebalances.
    """
    axes = mesh.axis_names
    kw = dict(kw_key)

    def local_solve(of, nf, cap, al, g0):
        res = hierarchical_assign(
            of, nf, cap / scale, al,
            n_groups=n_groups, coarse_g_init=g0, **kw,
        )
        return HierarchicalResult(
            assignment=res.assignment,
            group=res.group,
            overflow=jax.lax.psum(res.overflow, axes),
            coarse_g=jax.lax.pmean(res.coarse_g, axes),
            coarse_err=jax.lax.pmean(res.coarse_err, axes),
        )

    sharded = shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(P(axes, None), P(), P(), P(), P()),
        out_specs=_hier_out_specs(axes),
        check_vma=False,
    )

    # A name of its own: the device trace shows ``jit_mesh_cell_solve`` on
    # every device's plane, one execution a chunk step.
    def mesh_cell_solve(of, nf, cap, al, g0):
        return sharded(of, nf, cap, al, g0)

    return jax.jit(mesh_cell_solve)


def mesh_chunked_hierarchical_assign_timed(
    mesh: Mesh,
    obj_feat: jax.Array,
    node_feat: jax.Array,
    node_capacity: jax.Array,
    alive: jax.Array,
    *,
    n_groups: int,
    n_chunks: int,
    coarse_g_init: jax.Array | None = None,
    **kw,
) -> tuple[HierarchicalResult, list[float]]:
    """:func:`mesh_chunked_hierarchical_assign` with per-chunk host timings.

    Same split as :func:`chunked_hierarchical_assign_timed`: the
    ``lax.map`` form hides chunk boundaries inside one executable, so this
    twin loops the chunks on the host — each iteration dispatches one
    mesh-wide slab (every device solves its own cell of that chunk)
    through the cached jitted cell solver (:func:`_mesh_cell_solver`) and
    times dispatch+``block_until_ready``. The slab for chunk ``c`` is
    exactly the ``lax.map`` form's set of (device, chunk ``c``) cells —
    same rows per cell, same ``cap / (n_shards * n_chunks)`` division —
    so the composed result matches the single-executable form. The first
    chunk's timing carries the one-time compile: the compile-vs-exec
    signal SolveStats wants, now at mesh scale.
    """
    import time as _time

    n_shards = int(mesh.devices.size)
    n, d = obj_feat.shape
    assert n % (n_shards * n_chunks) == 0, (n, n_shards, n_chunks)
    cell = n // (n_shards * n_chunks)
    solve = _mesh_cell_solver(
        mesh, n_shards * n_chunks, n_groups, tuple(sorted(kw.items()))
    )
    # (shard, chunk, cell, d) view: slab c = every shard's chunk-c cell,
    # laid out shard-major so P(axes) sharding hands each device its own
    # cell — the exact row->cell mapping of the lax.map form. A host
    # (numpy) block stays on the host here: each slab is then cut on the
    # host and put straight onto its devices, where jnp.asarray would
    # commit the whole block to device 0 first and reshard from there.
    of = obj_feat.reshape(n_shards, n_chunks, cell, d)
    # Sync staged inputs BEFORE the timed loop (same reason as the chunked
    # twin): an async pending producer chain behind obj_feat is O(N) in
    # TOTAL rows and would drain inside chunk 0's timer, inflating the
    # "compile" number superlinearly with N — the exact signal the
    # composed solve exists to keep flat.
    jax.block_until_ready((of, node_feat, node_capacity, alive))
    shard_spec = NamedSharding(mesh, P(mesh.axis_names, None))
    rep_inputs = None
    assignments: list = []
    groups: list = []
    overflow = 0
    chunk_ms: list[float] = []
    res = None
    for c in range(n_chunks):
        t0 = _time.perf_counter()
        with stage("solve.mesh.inputs"):
            slab = of[:, c].reshape(n_shards * cell, d)
            if rep_inputs is None:
                slab, *rep_inputs = _mesh_inputs(
                    mesh, slab, node_feat, node_capacity, alive,
                    coarse_g_init, n_groups,
                )
            else:
                slab = jax.device_put(slab, shard_spec)
            # The transfer is the inputs' time, not the first cell's.
            jax.block_until_ready(slab)
        with stage("solve.mesh.cells"):
            res = solve(slab, *rep_inputs)
            jax.block_until_ready(res.assignment)
        chunk_ms.append(round((_time.perf_counter() - t0) * 1e3, 3))
        assignments.append(res.assignment)
        groups.append(res.group)
        overflow = overflow + res.overflow
    # Chunk results stack to (shard, chunk, cell) when interleaved back on
    # axis 1 — the shard-major global row order the input was reshaped from.
    # Each result is pulled shard by shard to the host and interleaved there:
    # stacked on the devices it would be one more program across all of them
    # for an array the caller reads on the host anyway.
    def interleaved(parts):
        return np.stack(
            [np.asarray(x).reshape(n_shards, cell) for x in parts], axis=1
        ).reshape(-1)

    with stage("solve.mesh.gather"):
        asn, grp = interleaved(assignments), interleaved(groups)
    return (
        HierarchicalResult(
            assignment=asn,
            group=grp,
            overflow=overflow,
            coarse_g=res.coarse_g,
            coarse_err=res.coarse_err,
        ),
        chunk_ms,
    )
