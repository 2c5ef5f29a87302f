"""Scaling-form (Sinkhorn-Knopp) solver: MXU matmuls, no per-iteration exp.

The log-domain solve (:mod:`rio_tpu.ops.sinkhorn`) pays two full
transcendental sweeps (exp) over the (objects x nodes) matrix per
iteration — on TPU that is VPU-bound, not HBM-bound. The classical scaling
form moves every transcendental *out* of the loop:

    K = exp(-C / eps)                  # once
    repeat:  u = a / (K @ v) ;  v = b / (K^T @ u)
    f = eps * log u ;  g = eps * log v

Each iteration is two matrix-vector products — pure MXU work, bandwidth
bound on reading ``K``. ``K`` can be stored bfloat16 (halving the traffic
again); products accumulate in float32.

Two implementations:

* :func:`scaling_sinkhorn` — plain XLA (two reads of K per iteration).
* :func:`pallas_scaling_sinkhorn` — fused Pallas kernel: the grid walks
  row blocks once per iteration, computing ``u_block = a / (K_block @ v)``
  and accumulating ``u_block^T @ K_block`` into the column marginal in VMEM
  scratch — ONE read of K per iteration, the bandwidth floor.

PROMOTED (r5): the fused kernel measured 1.19x the XLA loop by iteration
slope at 262144x1024 on TPU v5e (PALLAS_TPU.json ``pallas_scaling``:
1.297 vs 1.548 ms/iter; the log-domain pallas kernel LOST at 0.72x and
stays quarantined as a parity-tested reference). :func:`scaling_core_auto`
selects it on TPU in the bandwidth-bound regime; the bench solve tier and
any dense flat solve go through that dispatcher.

Numerics: with cost scale O(1) and eps >= ~0.03, exp(-C/eps) stays well
inside float32/bfloat16 range and the scalings stay finite; zero-mass rows
(padding) give u = 0 and dead columns v = 0, reproducing the log-domain
-inf conventions after the final log. Iterations are mathematically
identical to the log-domain updates, so results match within dtype
tolerance (see tests/test_scaling_sinkhorn.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sinkhorn import (
    SinkhornResult,
    _safe_log,
    marginal_err,
    normalize_marginals,
    pad_axis_to,
)

_NEG_INF = float("-inf")


def _potentials(u, v, eps):
    f = jnp.where(u > 0, eps * _safe_log(u), _NEG_INF)
    g = jnp.where(v > 0, eps * _safe_log(v), _NEG_INF)
    return f, g


def _warm_seed(g_init: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Effective warm seed and the global gauge ``s`` it is lowered by.

    Non-finite entries (dead columns of the previous solve) cold-fill to 0
    — EXACTLY what the log-domain solver does with its warm seed, so the
    two stay comparable. ``v0 = exp(g0 / eps)`` would overflow for large
    potentials, so the seed is gauged down by its max: ``v0 = exp((g0 - s)
    / eps)`` with every exponent <= 0. Unlike the log-domain solver —
    whose g-update recomputes g from scratch each iteration — the scaling
    updates are homogeneous (``u = a/(Kv)``, ``v = b/(K^T u)``), so the
    gauge PERSISTS through every iteration: the converged scalings come
    out as ``(u * e^{s/eps}, v * e^{-s/eps})`` relative to the ungauged
    warm solve, and the caller must correct the final potentials by
    ``f - s`` / ``g + s`` to match the log-domain reference. This is a
    GLOBAL scalar on the warm seed only — fully orthogonal to the
    per-row min-shift on the cost (which must stay per-row, see
    :func:`scaling_core`)."""
    g0 = jnp.where(jnp.isfinite(g_init), g_init.astype(jnp.float32), 0.0)
    return g0, jnp.max(g0)


@functools.partial(jax.jit, static_argnames=("eps", "n_iters", "kernel_dtype"))
def scaling_core(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    kernel_dtype=jnp.bfloat16,
    g_init: jax.Array | None = None,
):
    """The scaling iteration itself; returns ``(u, v, K, row_shift)``.

    ``row_shift`` is the (n,) per-row gauge shift subtracted from the cost
    before exponentiating (add it back to ``eps*log(u)`` to recover ``f``).

    Exposed separately from :func:`scaling_sinkhorn` so capacity-aware
    rounding can reuse the already-materialized kernel ``K`` (see
    :func:`rio_tpu.ops.sinkhorn.plan_rounded_assign_from_scaling`): the
    plan is ``P = diag(u) K diag(v)`` — re-deriving it from the cost
    matrix would re-read the fp32 cost (2x the bytes of a bf16 K) and
    re-do a transcendental sweep.

    ``g_init`` warm-starts ``v0`` from a previous solve's node potentials.
    The seed is gauged by its max entry (:func:`_warm_seed`) so the
    exponential never overflows; that gauge persists through the
    homogeneous iterations, so the returned ``(u, v)`` are the warm solve's
    scalings times ``(e^{s/eps}, e^{-s/eps})`` — callers that need
    log-domain-parity potentials correct by ``s`` (as
    :func:`scaling_sinkhorn` does). Exponents are clipped at -60 (below
    which a live column's seed would denormal to zero and the column would
    restart cold anyway).
    """
    cost = cost.astype(jnp.float32)
    a, b = normalize_marginals(row_mass, col_capacity)
    # PER-ROW min-shift: pure gauge (each row's shift is absorbed into that
    # row's u), keeps every row's best entry at exp(0)=1 — so no row can
    # underflow to all-zeros no matter the global cost range (a global
    # shift breaks down once range/eps >> 88: tail rows lose every entry
    # and their u explodes; observed at the 10M-object hierarchical tier).
    # Individual high-cost pairs may still underflow — acceptable, they are
    # effectively forbidden. The shift is folded back into f by
    # scaling_sinkhorn so the returned potentials match the log-domain
    # solver exactly, not just up to gauge.
    shift = jnp.min(cost, axis=1, keepdims=True)  # (n, 1)
    # Padding rows of +inf cost would make shift inf -> NaN in K; they
    # carry no mass, so pin their shift to 0.
    shift = jnp.where(jnp.isfinite(shift), shift, 0.0)
    cost = cost - shift
    K = jnp.exp(-cost / eps).astype(kernel_dtype)

    def body(carry, _):
        _, v = carry
        Kv = jnp.matmul(K, v.astype(kernel_dtype), preferred_element_type=jnp.float32)
        u = a / jnp.maximum(Kv, 1e-30)
        u = jnp.where(a > 0, u, 0.0)
        KTu = jnp.matmul(u.astype(kernel_dtype), K, preferred_element_type=jnp.float32)
        v = b / jnp.maximum(KTu, 1e-30)
        v = jnp.where(b > 0, v, 0.0)
        return (u, v), None

    u0 = jnp.zeros_like(a)
    if g_init is None:
        v0 = jnp.ones_like(b)
    else:
        g_seed, s = _warm_seed(g_init)
        v0 = jnp.exp(jnp.clip((g_seed - s) / eps, -60.0, 0.0))
    (u, v), _ = lax.scan(body, (u0, v0), None, length=n_iters)
    return u, v, K, shift[:, 0]


@functools.partial(jax.jit, static_argnames=("eps", "n_iters", "kernel_dtype"))
def scaling_sinkhorn(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    kernel_dtype=jnp.bfloat16,
    g_init: jax.Array | None = None,
) -> SinkhornResult:
    """Sinkhorn-Knopp in scaling form; returns log-domain potentials.

    Matches :func:`rio_tpu.ops.sinkhorn.sinkhorn` up to dtype tolerance
    (use ``kernel_dtype=jnp.float32`` for tightest parity) — including
    under ``g_init`` warm start: the warm seed's global gauge (see
    :func:`_warm_seed`) persists through the homogeneous scaling
    iterations and is undone here, so warm potentials agree with the
    warm log-domain reference, not just up to gauge.
    """
    u, v, _, shift = scaling_core(
        cost, row_mass, col_capacity, eps=eps, n_iters=n_iters,
        kernel_dtype=kernel_dtype, g_init=g_init,
    )
    cost = cost.astype(jnp.float32) - shift[:, None]
    _, b = normalize_marginals(row_mass, col_capacity)
    f, g = _potentials(u, v, eps)
    if g_init is not None:
        # Undo the warm gauge (f/g shift by ∓s; f+g is invariant, so the
        # marginal-err diagnostic below is unaffected either way).
        _, s = _warm_seed(g_init)
        f = jnp.where(jnp.isfinite(f), f - s, f)
        g = jnp.where(jnp.isfinite(g), g + s, g)
    err = marginal_err(cost, f, g, b, eps)  # shifted-cost/shifted-f pair
    f = jnp.where(jnp.isfinite(f), f + shift, f)  # undo the gauge shift
    return SinkhornResult(f=f, g=g, err=err)


# ---------------------------------------------------------------------------
# Fused Pallas iteration: one sweep of K per iteration
# ---------------------------------------------------------------------------


def _scaling_kernel(
    a_ref,      # (B, 1) row marginals block
    b_ref,      # (1, M) column marginals
    v_ref,      # (1, M) previous column scaling
    k_ref,      # (B, M) kernel block
    u_out_ref,  # (B, 1) new row scaling for this block
    v_out_ref,  # (1, M) new column scaling (written on last step)
    col_acc,    # (1, M) VMEM scratch: running K^T u partial
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        col_acc[:] = jnp.zeros_like(col_acc[:])

    # A matvec is bandwidth-bound (one FMA per element), so the VPU with an
    # explicit f32 multiply-reduce hits the same roofline as the MXU would —
    # and Mosaic lowers degenerate (B,M)x(1,M) dots poorly.
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:]  # (1, M)
    a = a_ref[:]  # (B, 1)
    Kv = jnp.sum(k * v, axis=1, keepdims=True)  # (B, 1)
    u = a / jnp.maximum(Kv, 1e-30)
    u = jnp.where(a > 0, u, 0.0)
    u_out_ref[:] = u
    col_acc[:] = col_acc[:] + jnp.sum(k * u, axis=0, keepdims=True)  # (1, M)

    @pl.when(step == pl.num_programs(0) - 1)
    def _finalize():
        b = b_ref[:]
        v_new = b / jnp.maximum(col_acc[:], 1e-30)
        v_out_ref[:] = jnp.where(b > 0, v_new, 0.0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_scaling_iteration(
    K: jax.Array,
    a: jax.Array,
    b: jax.Array,
    v: jax.Array,
    *,
    block_rows: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused scaling iteration: returns (u_new, v_new)."""
    n, m = K.shape
    assert n % block_rows == 0, (n, block_rows)
    grid = (n // block_rows,)
    u, v_new = pl.pallas_call(
        _scaling_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, m), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, m), jnp.float32)],
        interpret=interpret,
    )(a.reshape(n, 1), b.reshape(1, m), v.reshape(1, m), K)
    return u.reshape(n), v_new.reshape(m)


def pallas_scaling_core(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    kernel_dtype=jnp.bfloat16,
    block_rows: int = 1024,
    interpret: bool = False,
):
    """Fused-kernel drop-in for :func:`scaling_core`: ``(u, v, K, shift)``.

    Same contract as :func:`scaling_core` (the returned ``K`` is the
    UNPADDED bf16 kernel, reusable by the rounding pass), but each
    iteration is one HBM sweep of ``K`` instead of two. Promoted after the
    r5 slope head-to-head on TPU v5e measured 1.297 ms/iter fused vs 1.548
    XLA at 262144x1024 (PALLAS_TPU.json, ``pallas_vs_xla: 1.19``).

    ``interpret=True`` runs the kernel in the Pallas interpreter (tests,
    CPU rehearsal). It is never chosen for the caller: without it, a
    backend that cannot compile the kernel raises.
    """
    n, m = cost.shape
    cost = cost.astype(jnp.float32)
    a, b = normalize_marginals(row_mass, col_capacity)
    shift = jnp.min(cost, axis=1, keepdims=True)  # per-row gauge, see scaling_core
    shift = jnp.where(jnp.isfinite(shift), shift, 0.0)
    K = jnp.exp(-(cost - shift) / eps).astype(kernel_dtype)

    lane = 128
    n_pad = -(-n // block_rows) * block_rows
    m_pad = -(-m // lane) * lane
    K_p = pad_axis_to(pad_axis_to(K, n_pad, 0, 0.0), m_pad, 1, 0.0)
    a_p = pad_axis_to(a, n_pad, 0, 0.0)
    b_p = pad_axis_to(b, m_pad, 0, 0.0)

    def body(carry, _):
        _, v = carry
        return fused_scaling_iteration(
            K_p, a_p, b_p, v, block_rows=block_rows, interpret=interpret
        ), None

    v0 = pad_axis_to(jnp.ones((m,), jnp.float32), m_pad, 0, 0.0)
    u0 = jnp.zeros((n_pad,), jnp.float32)
    (u, v), _ = lax.scan(body, (u0, v0), None, length=n_iters)
    return u[:n], v[:m], K, shift[:, 0]


# The fused kernel's measured win is HBM-bandwidth reuse, so it only
# applies where K spills far past VMEM; below this element count the XLA
# loop is already cache/VMEM-resident and the pallas grid overhead loses.
_FUSED_MIN_ELEMS = 1 << 24  # 32 MB of bf16 K
# ...and only at WIDE column counts. The r5 TPU A/B: at m=1024 the fused
# kernel wins (1.19x by iteration slope at 262144x1024; 275.7 -> 226.8 ms
# single-call and 212.1 -> 204.6 ms chained at 1Mx1024), but at m=256 it
# LOSES 2.1x (33.3 -> 71.0 ms chained at 1M) — narrow blocks starve the
# sweep: per-grid-step work shrinks with m while the step count and the
# (1, m) accumulator round trips don't. Dispatch only where measured.
_FUSED_MIN_COLS = 1024


def scaling_impl_for(n: int, m: int, *, block_rows: int = 1024) -> str:
    """Which implementation :func:`scaling_core_auto` picks for (n, m)."""
    if (
        jax.default_backend() == "tpu"
        and n * m >= _FUSED_MIN_ELEMS
        and m >= _FUSED_MIN_COLS
        and n % block_rows == 0
    ):
        return "pallas_fused"
    return "xla"


def scaling_core_auto(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    kernel_dtype=jnp.bfloat16,
    block_rows: int = 1024,
):
    """Backend-aware :func:`scaling_core`: fused Pallas on TPU, XLA else.

    Selection is static per (backend, shape): on TPU with
    ``n*m >= 2**24`` (the bandwidth-bound regime the r5 slope measurement
    covers) the fused kernel runs; everywhere else — host CPUs, small
    problems, and any shape the kernel's row-block padding would inflate
    by >12.5% — the plain XLA loop does. Returns ``(u, v, K, shift)``
    either way.
    """
    n, m = cost.shape
    if scaling_impl_for(n, m, block_rows=block_rows) == "pallas_fused":
        return pallas_scaling_core(
            cost, row_mass, col_capacity, eps=eps, n_iters=n_iters,
            kernel_dtype=kernel_dtype, block_rows=block_rows,
        )
    return scaling_core(
        cost, row_mass, col_capacity, eps=eps, n_iters=n_iters,
        kernel_dtype=kernel_dtype,
    )


def pallas_scaling_sinkhorn(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    kernel_dtype=jnp.bfloat16,
    block_rows: int = 1024,
    interpret: bool = False,
) -> SinkhornResult:
    """Fused-kernel scaling Sinkhorn: one HBM sweep of K per iteration.

    Pads objects to a ``block_rows`` multiple (zero mass) and nodes to a
    lane multiple (zero capacity + zero kernel column, so padding attracts
    nothing); padding is sliced off the result.
    """
    u, v, _, shift = pallas_scaling_core(
        cost, row_mass, col_capacity, eps=eps, n_iters=n_iters,
        kernel_dtype=kernel_dtype, block_rows=block_rows, interpret=interpret,
    )
    cost = cost.astype(jnp.float32) - shift[:, None]
    _, b = normalize_marginals(row_mass, col_capacity)
    f, g = _potentials(u, v, eps)
    err = marginal_err(cost, f, g, b, eps)  # shifted-cost/shifted-f pair
    f = jnp.where(jnp.isfinite(f), f + shift, f)  # undo the gauge shift
    return SinkhornResult(f=f, g=g, err=err)
