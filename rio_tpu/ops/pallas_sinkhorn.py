"""Fused single-pass Sinkhorn iteration as a Pallas TPU kernel.

One entropic-OT iteration needs two reductions over the (objects x nodes)
cost matrix: a row log-sum-exp of ``(g - C)/eps`` (the ``f`` update) and a
column log-sum-exp of ``(f_new - C)/eps`` (the ``g`` update). Expressed in
plain XLA that is two full HBM sweeps of ``C`` per iteration — and at the
BASELINE scale (1M x 1k, 4 GB fp32) the solve is purely HBM-bandwidth
bound.

This kernel fuses both updates into ONE sweep: the grid walks row blocks;
each step (a) computes the block's ``f`` from the previous ``g`` and
(b) immediately folds the block's contribution into an *online* column
log-sum-exp (running max + rebased sum in VMEM scratch, the
flash-attention accumulation pattern). The final grid step materializes the
new ``g``. Net effect: half the HBM traffic of the unfused solve, which is
a ~2x iteration speedup where it matters.

Tests run it with ``interpret=True`` on the CPU mesh. Semantics match
:func:`rio_tpu.ops.sinkhorn.sinkhorn` (same math, same -inf conventions
for padding rows / dead nodes).
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

_NEG_INF = float("-inf")  # also the kernel-side padding convention


def _iteration_kernel(
    log_a_ref,  # (B, 1) block of row log-marginals
    log_b_ref,  # (1, M) full column log-marginals
    g_ref,      # (1, M) previous node potentials
    cost_ref,   # (B, M) cost block
    eps_ref,    # (1, 1) SMEM scalar
    f_out_ref,  # (B, 1) new row potentials for this block
    g_out_ref,  # (1, M) new node potentials (written on the last step)
    m_acc,      # (1, M) VMEM scratch: running column max
    s_acc,      # (1, M) VMEM scratch: running rebased column sum
):
    step = pl.program_id(0)
    eps = eps_ref[0, 0]
    cost = cost_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)  # (1, M)
    log_a = log_a_ref[:].astype(jnp.float32)  # (B, 1)

    @pl.when(step == 0)
    def _init():
        m_acc[:] = jnp.full_like(m_acc[:], _NEG_INF)
        s_acc[:] = jnp.zeros_like(s_acc[:])

    # ---- f update for this row block: row LSE of (g - C)/eps -------------
    z = (g - cost) / eps  # (B, M), g broadcast over rows
    zmax = jnp.max(z, axis=1, keepdims=True)  # (B, 1)
    zsafe = jnp.where(jnp.isfinite(zmax), zmax, 0.0)
    zsum = jnp.sum(jnp.exp(z - zsafe), axis=1, keepdims=True)
    row_lse = zsafe + jnp.log(jnp.maximum(zsum, 1e-30))
    f = eps * (log_a - row_lse)  # (B, 1)
    f = jnp.where(jnp.isfinite(log_a), f, _NEG_INF)
    f_out_ref[:] = f

    # ---- online column LSE of (f - C)/eps --------------------------------
    w = (f - cost) / eps  # (B, M), f broadcast over columns
    bmax = jnp.max(w, axis=0, keepdims=True)  # (1, M)
    new_m = jnp.maximum(m_acc[:], bmax)
    msafe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    # Rebase the running sum onto the new max; -inf old max means zero sum.
    rebase = jnp.where(
        jnp.isfinite(m_acc[:]), jnp.exp(m_acc[:] - msafe), 0.0
    )
    block_sum = jnp.sum(jnp.exp(w - msafe), axis=0, keepdims=True)
    s_acc[:] = s_acc[:] * rebase + block_sum
    m_acc[:] = new_m

    # ---- finalize g on the last grid step --------------------------------
    @pl.when(step == pl.num_programs(0) - 1)
    def _finalize():
        log_b = log_b_ref[:].astype(jnp.float32)
        msafe_f = jnp.where(jnp.isfinite(m_acc[:]), m_acc[:], 0.0)
        col_lse = msafe_f + jnp.log(jnp.maximum(s_acc[:], 1e-30))
        g_new = eps * (log_b - col_lse)
        g_out_ref[:] = jnp.where(jnp.isfinite(log_b), g_new, _NEG_INF)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_iteration(
    cost: jax.Array,
    log_a: jax.Array,
    log_b: jax.Array,
    g: jax.Array,
    eps: jax.Array,
    *,
    block_rows: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused Sinkhorn iteration: returns (f_new, g_new).

    ``cost`` is (N, M) with N divisible by ``block_rows`` and M a multiple
    of 128 (callers pad; see :func:`pallas_sinkhorn`).
    """
    n, m = cost.shape
    assert n % block_rows == 0, (n, block_rows)
    grid = (n // block_rows,)
    eps_arr = jnp.asarray(eps, jnp.float32).reshape(1, 1)
    f, g_new = pl.pallas_call(
        _iteration_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, m), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, m), jnp.float32),
            pltpu.VMEM((1, m), jnp.float32),
        ],
        interpret=interpret,
    )(log_a.reshape(n, 1), log_b.reshape(1, m), g.reshape(1, m), cost, eps_arr)
    return f.reshape(n), g_new.reshape(m)


def pallas_sinkhorn(
    cost: jax.Array,
    row_mass: jax.Array,
    col_capacity: jax.Array,
    *,
    eps: float = 0.05,
    n_iters: int = 50,
    block_rows: int = 512,
    interpret: bool = False,
) -> SinkhornResult:
    """Drop-in for :func:`rio_tpu.ops.sinkhorn.sinkhorn` using the fused
    Pallas kernel (single HBM sweep of the cost matrix per iteration).

    Pads the object axis to a ``block_rows`` multiple with zero-mass rows and
    the node axis to a 128 multiple with zero-capacity columns; padding never
    influences live potentials (-inf marginals contribute nothing to either
    log-sum-exp) and is sliced off the result. ``interpret=True`` runs the
    Pallas interpreter (tests); it is never chosen for the caller.
    """
    n, m = cost.shape
    cost = cost.astype(jnp.float32)
    a, b = normalize_marginals(row_mass, col_capacity)
    log_a = jnp.where(a > 0, _safe_log(a), -jnp.inf)
    log_b = jnp.where(b > 0, _safe_log(b), -jnp.inf)

    n_pad = -(-n // block_rows) * block_rows
    m_pad = -(-m // 128) * 128
    cost_p = pad_axis_to(pad_axis_to(cost, n_pad, 0, 0.0), m_pad, 1, 0.0)
    log_a_p = pad_axis_to(log_a, n_pad, 0, _NEG_INF)
    log_b_p = pad_axis_to(log_b, m_pad, 0, _NEG_INF)

    eps_arr = jnp.float32(eps)

    def body(carry, _):
        _, g = carry
        f, g_new = fused_iteration(
            cost_p, log_a_p, log_b_p, g, eps_arr,
            block_rows=block_rows, interpret=interpret,
        )
        return (f, g_new), None

    f0 = jnp.zeros((n_pad,), jnp.float32)
    # Padding columns must start at -inf, not 0: the first f-update's row
    # LSE would otherwise see phantom zero-cost nodes. Real columns start at
    # 0 even when dead (matching the unfused solve, whose first iteration
    # includes them before their -inf log_b zeroes them out).
    g0 = pad_axis_to(jnp.zeros((m,), jnp.float32), m_pad, 0, _NEG_INF)
    (f, g), _ = lax.scan(body, (f0, g0), None, length=n_iters)

    f = f[:n]
    g = g[:m]
    return SinkhornResult(f=f, g=g, err=marginal_err(cost, f, g, b, eps))
