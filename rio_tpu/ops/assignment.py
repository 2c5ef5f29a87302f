"""Cost-matrix construction and greedy balanced assignment.

The cost model replaces the reference's implicit placement policy (random
active server on client cache miss, ``rio-rs/src/client/mod.rs:255-262``;
unconditional self-assign on the receiving server,
``rio-rs/src/service.rs:241-253``) with an explicit objective:

  cost[i, j] = load_penalty * (node_load[j] / capacity[j])
             + affinity_penalty * (1 - affinity[i, j])
             + BIG * (1 - alive[j])

Dead nodes are priced out rather than masked so the matrix keeps a static
shape (cluster size changes do not recompile).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEAD_NODE_COST = 1e6


def integer_fair_quotas(
    cap_alive: np.ndarray, n: int, counts: np.ndarray | None = None
) -> np.ndarray:
    """Largest-remainder integer fair shares of ``n`` seats (host numpy).

    The delta-rebalance counterpart of the device-side quota math inside
    :func:`rio_tpu.ops.sinkhorn.exact_quota_repair`: per-node quotas
    proportional to schedulable capacity, floors plus one bonus unit for
    the ``n - sum(floors)`` largest remainders, summing to ``n`` EXACTLY.
    Same invariant as the device repair: NO global rescale of the raw
    shares (an fp rescale flips floor/remainder units on exact-integer
    columns at large scale; see the r4 note there). Zero-capacity nodes
    get zero share and zero quota. ``counts`` (rows each column holds now)
    settles remainder ties between columns of one capacity the way the device
    repair settles them: the fuller column keeps the unit it has. Without it
    the unit goes to the lower index, and a solve that alternates with the
    device's moves a row for every unit the two rules place differently.
    """
    cap = np.maximum(np.asarray(cap_alive, np.float64), 0.0)
    total = cap.sum()
    if n <= 0 or total <= 0.0:
        return np.zeros(cap.shape[0], np.int64)
    target = cap / total * n
    quota = np.floor(target).astype(np.int64)
    short = n - int(quota.sum())
    if short > 0:
        # Remainder ties prefer the higher-capacity column (deterministic,
        # and a bonus unit belongs where it displaces least).
        keys = (-cap, -(target - quota))
        if counts is not None:
            keys = (-np.asarray(counts, np.float64), *keys)
        rem_order = np.lexsort(keys)
        quota[rem_order[:short]] += 1
    return quota


def residual_capacity_assign(
    score: np.ndarray, residual: np.ndarray
) -> np.ndarray:
    """Seat D displaced objects into integer residual quotas (host numpy).

    ``residual[j]`` is node j's remaining quota after undisplaced objects
    kept their seats (``sum(residual)`` must equal the displaced count);
    ``score[j]`` orders the fill (typically ``base_cost - g`` with the
    warm-started node potentials, so the cheapest nodes absorb first).
    Objects within the displaced set are interchangeable under the flat
    cost model — every feasible fill has identical transport cost — so
    laying them out as contiguous per-node runs is exact, O(D), and
    deterministic. Returns (D,) int32 node indices.
    """
    residual = np.asarray(residual, np.int64)
    order = np.argsort(np.asarray(score, np.float64), kind="stable")
    return np.repeat(order, residual[order]).astype(np.int32)


def build_cost_matrix(
    node_load: jax.Array,
    node_capacity: jax.Array,
    alive: jax.Array,
    affinity: jax.Array | None = None,
    *,
    load_weight: float = 1.0,
    affinity_weight: float = 0.25,
) -> jax.Array:
    """(n_objects x n_nodes) cost from liveness + relative load (+ affinity).

    Args:
      node_load: (n_nodes,) current absorbed load per node.
      node_capacity: (n_nodes,) capacity per node (0 for retired slots).
      alive: (n_nodes,) 1.0 if the member is active (gossip liveness,
        reference ``peer_to_peer.rs:101-112``), else 0.0.
      affinity: optional (n_objects, n_nodes) in [0, 1]; 1 = strongly prefer
        (e.g. state locality / cache warmth). If None, costs are per-node
        only and the result is broadcast to (1, n_nodes).
    """
    cap = jnp.maximum(node_capacity.astype(jnp.float32), 1e-6)
    per_node = load_weight * (node_load.astype(jnp.float32) / cap)
    per_node = per_node + DEAD_NODE_COST * (1.0 - alive.astype(jnp.float32))
    if affinity is None:
        return per_node[None, :]
    aff = affinity_weight * (1.0 - affinity.astype(jnp.float32))
    return per_node[None, :] + aff


def assign_from_potentials(cost_rows: jax.Array, g: jax.Array) -> jax.Array:
    """Incremental placement: argmin_j cost[i,j] - g[j] with cached potentials.

    This is the warm-start fast path — new/churned objects are placed against
    the last solve's node potentials without re-running Sinkhorn.
    """
    g = jnp.where(jnp.isfinite(g), g, -jnp.inf)
    return jnp.argmin(cost_rows.astype(jnp.float32) - g[None, :], axis=1).astype(jnp.int32)


@jax.jit
def rank_within_group(
    keys: jax.Array, group_keys: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-element rank among equal group keys, via one stable sort.

    Elements are ordered by a stable sort of ``keys``; ranks count within
    runs of equal ``group_keys`` (default: ``keys`` themselves — pass a
    composite sort key plus separate group keys to control ordering WITHIN
    each group, e.g. preferred-first eviction). Returns
    ``(order, sorted_group_keys, rank_sorted)``. Shared by the churn-aware
    greedy rebalance (keep-within-fair-share) and the exact quota repair
    (keep-within-quota) — the scan is subtle enough that one copy is plenty.
    """
    order = jnp.argsort(keys, stable=True)
    sorted_groups = (keys if group_keys is None else group_keys)[order]
    pos = jnp.arange(keys.shape[0])
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_groups[1:] != sorted_groups[:-1]]
    )
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos, 0)
    )
    return order, sorted_groups, (pos - group_start).astype(jnp.int32)


@jax.jit
def greedy_balanced_assign(
    cost: jax.Array,
    row_mass: jax.Array,
    node_capacity: jax.Array,
    node_load: jax.Array | None = None,
) -> jax.Array:
    """Capacity-proportional waterfilling: the cheap balanced-assignment tier.

    Nodes are sorted by their (column-mean) cost; each node absorbs mass up to
    its *headroom* — the gap between its capacity-fair share of the total
    (existing + incoming) load and its current load. Objects are laid onto
    this sorted partition by cumulative-mass position (``searchsorted``), so
    the result is exactly capacity-balanced, deterministic, and free of the
    oscillation/herding failure modes of simultaneous penalized argmin.
    Zero-capacity (dead) nodes get zero-width intervals and are never chosen.

    Per-object affinity is intentionally ignored here — this tier trades
    placement quality for a single O(N log M) pass; the Sinkhorn tier
    (:func:`rio_tpu.ops.sinkhorn.sinkhorn_assign`) honors full per-object
    costs.
    """
    cost = cost.astype(jnp.float32)
    mass = jnp.maximum(row_mass.astype(jnp.float32), 0.0)
    cap = jnp.maximum(node_capacity.astype(jnp.float32), 0.0)
    n_nodes = cost.shape[1]
    load = (
        jnp.zeros((n_nodes,), jnp.float32)
        if node_load is None
        else node_load.astype(jnp.float32)
    )

    total_mass = jnp.sum(mass)
    cap_share = cap / jnp.maximum(jnp.sum(cap), 1e-30)
    fair = (total_mass + jnp.sum(load)) * cap_share
    headroom = jnp.maximum(fair - load, 0.0)
    # If the cluster is already at/over fair everywhere, fall back to pure
    # capacity shares so the incoming batch still spreads proportionally.
    total_headroom = jnp.sum(headroom)
    width = jnp.where(total_headroom > 1e-30, headroom, cap_share * total_mass)
    # Scale widths to cover exactly the incoming mass (overflow spreads pro rata).
    width = width * (total_mass / jnp.maximum(jnp.sum(width), 1e-30))

    score = jnp.mean(cost, axis=0) + DEAD_NODE_COST * (cap <= 0)
    order = jnp.argsort(score)
    boundaries = jnp.cumsum(width[order])
    # Mid-mass position of each object avoids boundary ties on zero-width bins.
    pos = jnp.cumsum(mass) - 0.5 * mass
    idx = jnp.clip(jnp.searchsorted(boundaries, pos, side="left"), 0, n_nodes - 1)
    return order[idx].astype(jnp.int32)
