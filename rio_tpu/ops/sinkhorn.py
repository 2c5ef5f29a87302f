"""Log-domain Sinkhorn (entropic optimal transport) for object placement.

Solves ``min_P <C, P> - eps * H(P)`` subject to ``P @ 1 = a`` (each object
carries its load mass) and ``P.T @ 1 = b`` (each node absorbs mass up to its
capacity share). The optimal plan is ``P = exp((f + g - C) / eps)`` for dual
potentials ``f`` (objects) and ``g`` (nodes); the hard assignment for object
``i`` is ``argmin_j C[i, j] - g[j]`` — it depends on the *node* potentials
only, which is what makes warm-started incremental placement cheap: a new
object needs one cost row and one argmin against the cached ``g``.

TPU notes:
- iterations run under ``lax.scan`` (one traced body, no Python loop);
- all reductions are float32 log-sum-exp (stable in bf16-heavy pipelines);
- shapes are static; callers pad the object axis to a bucket size so XLA
  compiles once per bucket, not once per batch.

This replaces the reference's per-request SQL lookup/self-assign policy
(``rio-rs/src/service.rs:193-254``) with a batched on-device solve; the
``ObjectPlacement`` trait boundary (``rio-rs/src/object_placement/mod.rs:39-56``)
is preserved by :class:`rio_tpu.object_placement.jax_placement.JaxObjectPlacement`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class SinkhornResult(NamedTuple):
    """Dual potentials and diagnostics from a Sinkhorn solve."""

    f: jax.Array  # (n_objects,) object potentials, float32
    g: jax.Array  # (n_nodes,) node potentials, float32
    err: jax.Array  # scalar: final L1 column-marginal violation


_NEG_INF = float("-inf")


def _safe_log(x: jax.Array) -> jax.Array:
    return jnp.log(jnp.maximum(x, 1e-30))


def normalize_marginals(row_mass: jax.Array, col_capacity: jax.Array):
    """Scale both marginals to unit total mass (float32)."""
    a = row_mass.astype(jnp.float32)
    b = col_capacity.astype(jnp.float32)
    a = a / jnp.maximum(jnp.sum(a), 1e-30)
    b = b / jnp.maximum(jnp.sum(b), 1e-30)
    return a, b


def marginal_err(cost: jax.Array, f: jax.Array, g: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    """L1 column-marginal violation of the implied plan (diagnostic)."""
    log_p = (f[:, None] + g[None, :] - cost.astype(jnp.float32)) / eps
    col = jnp.sum(jnp.exp(jnp.where(jnp.isfinite(log_p), log_p, -jnp.inf)), axis=0)
    return jnp.sum(jnp.abs(col - b))


def pad_axis_to(x: jax.Array, size: int, axis: int, fill: float) -> jax.Array:
    """Pad ``x`` along ``axis`` up to ``size`` with ``fill`` (no-op if equal)."""
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def sinkhorn(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    g_init: jax.Array | None = None,
) -> SinkhornResult:
    """Run ``n_iters`` log-domain Sinkhorn iterations.

    Args:
      cost: (n_objects, n_nodes) cost matrix (any float dtype; accumulated f32).
      row_mass: (n_objects,) per-object mass (e.g. normalized load); rows with
        zero mass are padding and are ignored.
      col_capacity: (n_nodes,) per-node capacity share; columns with zero
        capacity (dead nodes) receive -inf potential and attract nothing.
      eps: entropic regularizer. Smaller = sharper assignment, slower
        convergence; 0.02-0.1 of the cost scale works well.
      n_iters: fixed iteration count (static for ``lax.scan``).
      g_init: optional (n_nodes,) warm-start node potentials from a previous
        solve (e.g. the cached ``g`` of an incremental rebalance). Only the
        FIRST f-update consumes it — the g-update recomputes g fully each
        iteration — so a good seed buys convergence in a handful of
        iterations while a stale one costs nothing but those iterations.
        Non-finite entries (dead columns from the previous solve) are
        treated as cold (0); the column marginals of THIS solve decide
        liveness, never the seed.
    """
    cost = cost.astype(jnp.float32)
    a, b = normalize_marginals(row_mass, col_capacity)
    log_a = jnp.where(a > 0, _safe_log(a), -jnp.inf)
    log_b = jnp.where(b > 0, _safe_log(b), -jnp.inf)

    def body(carry, _):
        f, g = carry
        # f-update: f_i = eps*(log a_i - LSE_j((g_j - C_ij)/eps))
        f = eps * (log_a - jax.nn.logsumexp((g[None, :] - cost) / eps, axis=1))
        f = jnp.where(jnp.isfinite(log_a), f, -jnp.inf)
        # g-update: g_j = eps*(log b_j - LSE_i((f_i - C_ij)/eps))
        g = eps * (log_b - jax.nn.logsumexp((f[:, None] - cost) / eps, axis=0))
        g = jnp.where(jnp.isfinite(log_b), g, -jnp.inf)
        return (f, g), None

    f0 = jnp.zeros(cost.shape[0], jnp.float32)
    if g_init is None:
        g0 = jnp.zeros(cost.shape[1], jnp.float32)
    else:
        g0 = jnp.where(
            jnp.isfinite(g_init), g_init.astype(jnp.float32), 0.0
        )
    (f, g), _ = lax.scan(body, (f0, g0), None, length=n_iters)
    return SinkhornResult(f=f, g=g, err=marginal_err(cost, f, g, b, eps))


@jax.jit
def plan_rounded_assign(cost: jax.Array, f: jax.Array, g: jax.Array, eps: float = 0.05) -> jax.Array:
    """Capacity-aware hard rounding of the soft transport plan.

    Row-argmax rounding of ``P = exp((f+g-C)/eps)`` collapses under cost
    ties (every identical row picks the same node, violating capacity).
    Instead, object ``i`` inverts its row's CDF at the deterministic quantile
    ``(i+0.5)/n``: aggregate node loads then match the plan's column
    marginals — i.e. capacities — while identical rows spread contiguously.
    Padding rows (``f = -inf``) fall back to the plan-uniform distribution of
    live columns; callers slice them off. Quantiles are taken over the *real*
    rows only — ranking by position among finite-``f`` rows — so bucket
    padding never skews the spread toward low-cumulative nodes.
    """
    cost = cost.astype(jnp.float32)
    is_real = jnp.isfinite(f)
    logit = (f[:, None] + g[None, :] - cost) / eps
    alive_cols = jnp.isfinite(g)
    logit = jnp.where(
        is_real[:, None],
        logit,
        jnp.where(alive_cols[None, :], 0.0, -jnp.inf),
    )
    p = jax.nn.softmax(logit, axis=1)
    cum = jnp.cumsum(p, axis=1)
    realf = is_real.astype(jnp.float32)
    n_real = jnp.maximum(jnp.sum(realf), 1.0)
    rank = jnp.cumsum(realf) - 1.0  # 0..n_real-1 over real rows
    u = jnp.where(is_real, (rank + 0.5) / n_real, 0.5)
    idx = jnp.sum((cum < u[:, None]).astype(jnp.int32), axis=1)
    return jnp.clip(idx, 0, cost.shape[1] - 1).astype(jnp.int32)


@jax.jit
def plan_rounded_assign_from_scaling(
    K: jax.Array, u: jax.Array, v: jax.Array
) -> jax.Array:
    """:func:`plan_rounded_assign`, but from the scaling-form state.

    The soft plan is ``P = diag(u) K diag(v)`` with ``K = exp(-C'/eps)``
    already materialized by :func:`rio_tpu.ops.scaling.scaling_core` —
    mathematically the same ``exp((f+g-C)/eps)`` the potential form
    exponentiates, so the CDF-inversion rounding below is identical up to
    kernel dtype. Reading the (usually bfloat16) ``K`` instead of the
    float32 cost halves the rounding pass's HBM traffic and removes its
    transcendental sweep — it is the difference between the solve fitting
    the <50 ms class at 1M x 1k or not.

    Padding rows (``u == 0``) spread uniformly over live columns
    (``v > 0``), exactly as the potential-form rounding treats ``f=-inf``.
    """
    u = u.astype(jnp.float32)
    v = v.astype(jnp.float32)
    is_real = u > 0
    alive = (v > 0).astype(jnp.float32)
    p = u[:, None] * K.astype(jnp.float32) * v[None, :]
    p = jnp.where(is_real[:, None], p, alive[None, :])
    # Row-normalize through the cumulative sum: invert each row's CDF at
    # the object's deterministic quantile among REAL rows (plan marginals
    # match capacities, identical rows spread contiguously).
    cum = jnp.cumsum(p, axis=1)
    total = jnp.maximum(cum[:, -1:], 1e-30)
    realf = is_real.astype(jnp.float32)
    n_real = jnp.maximum(jnp.sum(realf), 1.0)
    rank = jnp.cumsum(realf) - 1.0
    q = jnp.where(is_real, (rank + 0.5) / n_real, 0.5)
    idx = jnp.sum((cum < q[:, None] * total).astype(jnp.int32), axis=1)
    return jnp.clip(idx, 0, K.shape[1] - 1).astype(jnp.int32)


@jax.jit
def exact_quota_repair(
    idx: jax.Array,
    expected_counts: jax.Array,
    prefer_keep: jax.Array | None = None,
    tie_counts: jax.Array | None = None,
) -> jax.Array:
    """Make a rounded assignment match integer column quotas EXACTLY.

    CDF-inversion rounding matches the soft plan's column marginals only in
    expectation — per-column counts carry ~sqrt(fair) binomial noise, so the
    max load overshoots fair share by ~3 sigma (measured +33% at fair=128).
    This repair computes integer quotas from the soft marginals (largest-
    remainder method), KEEPS every object whose column is within quota
    (within-column rank < quota), and re-slots only the excess into the
    under-quota columns — the minimal move set (~the total overshoot, a
    few percent), not a global re-slotting. Zero-expected (dead) columns
    get zero quota and end up empty.

    Args:
      idx: (n,) int32 initial assignment (e.g. from plan rounding).
      expected_counts: (m,) float expected objects per column (soft column
        marginals x n); must sum to ~n.
      prefer_keep: optional (n,) bool — objects to evict LAST from an
        over-quota column. A churn re-solve passes "rounded to its current
        seat", so quota eviction lands on objects that were moving anyway
        and the repair adds ~zero extra churn.
      tie_counts: optional (m,) occupancy that settles remainder ties in
        place of ``idx``'s own. A churn re-solve passes the CURRENT
        directory's: the rounded plan's occupancy differs from it by the
        plan's rounding noise, and a unit awarded by that noise moves a row
        (at 1,048,576 x 1,024 some 460 of 400 units' worth, PERF.md PR 27).
    """
    from .assignment import rank_within_group

    n = idx.shape[0]
    m = expected_counts.shape[0]
    counts = jnp.bincount(idx, length=m)
    scaled = jnp.maximum(expected_counts.astype(jnp.float32), 0.0)
    # NO global rescale to sum-n here: multiplying every column by
    # n/sum(scaled) perturbs each by the fp32 summation error, and at
    # 2^24-scale totals that flips floor/remainder units on EXACT-integer
    # columns — observed r4 as a padding-sentinel column whose quota came
    # out one above the padding count, seating a real object on a
    # non-node. Raw marginals keep integer columns' floors exact (their
    # remainder is 0, so they never draw a largest-remainder bonus), and
    # the integer shortfall below absorbs caller drift exactly. The clip
    # guards the documented "sums to ~n" contract: a wildly undershooting
    # caller now underfills (refill clamps) instead of being silently
    # renormalized.
    base = jnp.floor(scaled).astype(jnp.int32)
    rem = scaled - base
    short = jnp.clip(n - jnp.sum(base), 0, m)
    # Largest remainders get the leftover units; remainder ties prefer the
    # MORE-occupied column (awarding a tied bonus to an empty column would
    # displace a seated object for no quota reason — churn, not repair).
    rem_order = jnp.lexsort((-(counts if tie_counts is None else tie_counts), -rem))
    bonus = (
        jnp.zeros((m,), jnp.int32)
        .at[rem_order]
        .set((jnp.arange(m) < short).astype(jnp.int32))
    )
    quota = base + bonus

    # Within-column rank via one stable sort (shared with the greedy
    # churn-aware rebalance): keep iff rank < quota[column]. With a
    # prefer_keep mask, sort by (column, not-preferred) so preferred
    # objects take the low ranks — eviction order is preferred-last.
    if prefer_keep is None:
        order, sorted_idx, rank = rank_within_group(idx)
    else:
        composite = idx.astype(jnp.int32) * 2 + (
            1 - prefer_keep.astype(jnp.int32)
        )
        order, sorted_idx, rank = rank_within_group(composite, idx)
    keep = rank < quota[sorted_idx]

    # Excess objects fill the under-quota columns in cumulative order.
    deficit = jnp.maximum(quota - counts, 0)
    bounds = jnp.cumsum(deficit)
    disp_rank = jnp.cumsum((~keep).astype(jnp.int32)) - 1
    refill = jnp.clip(
        jnp.searchsorted(bounds, disp_rank, side="right"), 0, m - 1
    )
    col_sorted = jnp.where(keep, sorted_idx, refill.astype(idx.dtype))
    return jnp.zeros_like(idx).at[order].set(col_sorted)


def route_sentinel_spill(
    idx: jax.Array, is_real: jax.Array, sentinel: int, capacity: jax.Array
) -> jax.Array:
    """Reseat real rows that quota repair left on the padding sentinel.

    Bucket-shaped solves route padding rows through a sentinel column
    (index ``sentinel``) whose quota is the padding count. Two drifts can
    seat a REAL row there instead: a float32 largest-remainder quota one
    unit above the padding count (observed r4 at the 2^24 bucket boundary
    — the root fix in :func:`exact_quota_repair` keeps integer columns
    exact, so this is belt-and-braces for callers whose expected marginals
    are not exact integers), and the repair refill's clip spilling into
    the last column when caller marginals undershoot. Downstream index
    lookups would otherwise crash (flat path) or silently clamp onto a
    possibly-dead neighbor (``take_along_axis`` in the hierarchical fine
    stage). The drift is at most a unit or two, so reseating spilled rows
    on the highest-capacity live column preserves balance within that
    drift. ONE implementation shared by every bucket-shaped caller
    (``JaxObjectPlacement`` and the hierarchical fine stage) — the guard
    semantics must never diverge between solvers.

    Args:
      idx: (n,) int32 assignment after quota repair.
      is_real: (n,) bool — real rows (padding rows keep the sentinel; they
        are dropped or sliced off by the caller).
      sentinel: first non-column index; anything >= it is a spill.
      capacity: (m,) effective capacity (zero on dead columns) used to
        pick the fallback seat.
    """
    spill = is_real & (idx >= sentinel)
    fallback = jnp.argmax(capacity).astype(idx.dtype)
    return jnp.where(spill, fallback, idx)


def sinkhorn_assign(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
) -> tuple[jax.Array, SinkhornResult]:
    """Solve and extract hard assignments ``argmin_j C[i,j] - g[j]``.

    Returns (assignment (n_objects,) int32, SinkhornResult). Dead nodes
    (zero capacity) are never chosen because their ``g`` is -inf.
    """
    res = sinkhorn(cost, row_mass, col_capacity, eps=eps, n_iters=n_iters)
    g = jnp.where(jnp.isfinite(res.g), res.g, -jnp.inf)
    assignment = jnp.argmin(cost.astype(jnp.float32) - g[None, :], axis=1)
    return assignment.astype(jnp.int32), res
