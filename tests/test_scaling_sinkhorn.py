"""Parity: scaling-form (Sinkhorn-Knopp) solvers vs the log-domain solve.

The scaling iterations are mathematically identical to the log-domain
updates, so with a float32 kernel the potentials must agree tightly; the
fused Pallas version (interpret mode on the CPU test mesh) must agree with
the XLA scaling version bit-for-mathematically."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rio_tpu.ops.scaling import (
    fused_scaling_iteration,
    pallas_scaling_sinkhorn,
    scaling_sinkhorn,
)
from rio_tpu.ops.sinkhorn import plan_rounded_assign, sinkhorn


def _problem(key, n, m, dead_nodes=0, padded_rows=0):
    k1, k2, k3 = jax.random.split(key, 3)
    cost = jax.random.uniform(k1, (n, m), jnp.float32)
    mass = jax.random.uniform(k2, (n,), jnp.float32) + 0.1
    if padded_rows:
        mass = mass.at[-padded_rows:].set(0.0)
    cap = jax.random.uniform(k3, (m,), jnp.float32) + 0.5
    if dead_nodes:
        cap = cap.at[:dead_nodes].set(0.0)
    return cost, mass, cap


@pytest.mark.parametrize("n,m", [(64, 128), (96, 130)])
def test_scaling_matches_log_domain(n, m):
    cost, mass, cap = _problem(jax.random.PRNGKey(0), n, m)
    ref = sinkhorn(cost, mass, cap, eps=0.08, n_iters=25)
    out = scaling_sinkhorn(
        cost, mass, cap, eps=0.08, n_iters=25, kernel_dtype=jnp.float32
    )
    np.testing.assert_allclose(np.asarray(out.f), np.asarray(ref.f), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(out.g), np.asarray(ref.g), rtol=1e-3, atol=1e-3)


def test_scaling_matches_log_domain_offset_costs():
    """f-parity must survive costs with a negative / shifted minimum.

    The scaling solvers gauge-shift by min(cost) internally; the shift must
    be folded back into f (the hierarchical mode's normalized -(feat@feat)
    costs have a negative min, where an unshifted f would diverge from the
    log-domain reference by -min(cost))."""
    cost, mass, cap = _problem(jax.random.PRNGKey(7), 64, 96)
    cost = cost * 2.0 - 1.7  # min well below zero
    ref = sinkhorn(cost, mass, cap, eps=0.08, n_iters=25)
    for solver in (
        lambda: scaling_sinkhorn(
            cost, mass, cap, eps=0.08, n_iters=25, kernel_dtype=jnp.float32
        ),
        lambda: pallas_scaling_sinkhorn(
            cost, mass, cap, eps=0.08, n_iters=25,
            kernel_dtype=jnp.float32, block_rows=16, interpret=True,
        ),
    ):
        out = solver()
        np.testing.assert_allclose(
            np.asarray(out.f), np.asarray(ref.f), rtol=1e-3, atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(out.g), np.asarray(ref.g), rtol=1e-3, atol=1e-3
        )


def test_sharded_scaling_offset_costs_f_parity():
    from rio_tpu.parallel import make_mesh, sharded_scaling_sinkhorn

    mesh = make_mesh(jax.devices()[:8])
    cost, mass, cap = _problem(jax.random.PRNGKey(8), 64, 96)
    cost = cost - 0.9
    ref = sinkhorn(cost, mass, cap, eps=0.08, n_iters=25)
    f, g = sharded_scaling_sinkhorn(
        mesh, cost, mass, cap, eps=0.08, n_iters=25, kernel_dtype=jnp.float32
    )
    np.testing.assert_allclose(np.asarray(f), np.asarray(ref.f), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref.g), rtol=1e-3, atol=1e-3)


def test_scaling_dead_nodes_and_padding():
    cost, mass, cap = _problem(jax.random.PRNGKey(1), 48, 96, dead_nodes=3, padded_rows=5)
    ref = sinkhorn(cost, mass, cap, eps=0.06, n_iters=30)
    out = scaling_sinkhorn(cost, mass, cap, eps=0.06, n_iters=30, kernel_dtype=jnp.float32)
    assert np.all(np.isneginf(np.asarray(out.g[:3])))
    assert np.all(np.isneginf(np.asarray(out.f[-5:])))
    np.testing.assert_allclose(np.asarray(out.g[3:]), np.asarray(ref.g[3:]), rtol=1e-3, atol=1e-3)
    a1 = plan_rounded_assign(cost, out.f, out.g, 0.06)
    a2 = plan_rounded_assign(cost, ref.f, ref.g, 0.06)
    assert np.mean(np.asarray(a1) == np.asarray(a2)) > 0.95


@pytest.mark.parametrize("n,m,block", [(64, 128, 8), (96, 130, 32), (40, 100, 16)])
def test_pallas_scaling_matches_xla_scaling(n, m, block):
    cost, mass, cap = _problem(jax.random.PRNGKey(2), n, m)
    ref = scaling_sinkhorn(cost, mass, cap, eps=0.07, n_iters=25, kernel_dtype=jnp.float32)
    out = pallas_scaling_sinkhorn(
        cost, mass, cap, eps=0.07, n_iters=25,
        kernel_dtype=jnp.float32, block_rows=block, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out.f), np.asarray(ref.f), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out.g), np.asarray(ref.g), rtol=1e-4, atol=1e-4)


def test_pallas_scaling_bf16_close_enough_for_assignment():
    cost, mass, cap = _problem(jax.random.PRNGKey(3), 128, 128)
    ref = sinkhorn(cost, mass, cap, eps=0.08, n_iters=25)
    out = pallas_scaling_sinkhorn(
        cost, mass, cap, eps=0.08, n_iters=25,
        kernel_dtype=jnp.bfloat16, block_rows=32, interpret=True,
    )
    a1 = plan_rounded_assign(cost, out.f, out.g, 0.08)
    a2 = plan_rounded_assign(cost, ref.f, ref.g, 0.08)
    # bf16 kernel may flip near-ties; the bulk of the assignment must agree.
    assert np.mean(np.asarray(a1) == np.asarray(a2)) > 0.9


def test_fused_scaling_iteration_single_step():
    n, m = 32, 128
    key = jax.random.PRNGKey(4)
    K = jax.random.uniform(key, (n, m), jnp.float32) + 0.01
    a = jnp.full((n,), 1.0 / n)
    b = jnp.full((m,), 1.0 / m)
    v_prev = jax.random.uniform(jax.random.PRNGKey(5), (m,)) + 0.5
    u, v = fused_scaling_iteration(K, a, b, v_prev, block_rows=8, interpret=True)
    u_ref = a / (K @ v_prev)
    v_ref = b / (K.T @ u_ref)
    np.testing.assert_allclose(np.asarray(u), np.asarray(u_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-5)


def test_sharded_scaling_matches_single_device():
    import pytest

    if len(jax.devices()) < 8:
        pytest.skip("needs 8-device mesh")
    from rio_tpu.parallel import make_mesh, shard_cost, sharded_scaling_sinkhorn

    n, m = 128, 64
    cost, mass, cap = _problem(jax.random.PRNGKey(6), n, m, dead_nodes=2)
    single = scaling_sinkhorn(
        cost, mass, cap, eps=0.07, n_iters=25, kernel_dtype=jnp.float32
    )
    mesh = make_mesh(jax.devices()[:8])
    f, g = sharded_scaling_sinkhorn(
        mesh, shard_cost(mesh, cost), mass, cap,
        eps=0.07, n_iters=25, kernel_dtype=jnp.float32,
    )
    np.testing.assert_allclose(np.asarray(g), np.asarray(single.g), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f), np.asarray(single.f), rtol=1e-4, atol=1e-4)


def test_plan_rounding_from_scaling_state_matches_potential_form():
    """K-reuse rounding == potential-form rounding (the bench hot path).

    ``plan_rounded_assign_from_scaling`` reads the already-materialized
    bf16 kernel instead of re-deriving exp((f+g-C)/eps) from the fp32 cost;
    with a float32 kernel the assignments must be identical, with bfloat16
    near-identical and equally balanced.
    """
    import numpy as np

    from rio_tpu.ops import (
        plan_rounded_assign,
        plan_rounded_assign_from_scaling,
        scaling_core,
        scaling_sinkhorn,
    )

    key = jax.random.PRNGKey(3)
    n, m = 2048, 128
    cost = jax.random.uniform(key, (n, m))
    mass, cap = jnp.ones((n,)), jnp.ones((m,))
    kw = dict(eps=0.05, n_iters=25)

    res = scaling_sinkhorn(cost, mass, cap, kernel_dtype=jnp.float32, **kw)
    base = np.asarray(plan_rounded_assign(cost, res.f, res.g, 0.05))

    u, v, K, _ = scaling_core(cost, mass, cap, kernel_dtype=jnp.float32, **kw)
    exact = np.asarray(plan_rounded_assign_from_scaling(K, u, v))
    assert (exact == base).all()

    u, v, K, _ = scaling_core(cost, mass, cap, kernel_dtype=jnp.bfloat16, **kw)
    approx = np.asarray(plan_rounded_assign_from_scaling(K, u, v))
    assert (approx == base).mean() > 0.98
    loads_base = np.bincount(base, minlength=m)
    loads_approx = np.bincount(approx, minlength=m)
    assert abs(int(loads_approx.max()) - int(loads_base.max())) <= 2


def test_plan_rounding_from_scaling_padding_and_dead_columns():
    """Padding rows (u=0) spread over live columns; dead columns never chosen."""
    import numpy as np

    from rio_tpu.ops import plan_rounded_assign_from_scaling, scaling_core

    key = jax.random.PRNGKey(5)
    n, m, n_real = 512, 16, 300
    cost = jax.random.uniform(key, (n, m))
    mass = jnp.concatenate([jnp.ones((n_real,)), jnp.zeros((n - n_real,))])
    cap = jnp.concatenate([jnp.ones((m - 4,)), jnp.zeros((4,))])  # 4 dead
    u, v, K, _ = scaling_core(cost, mass, cap, eps=0.05, n_iters=25)
    idx = np.asarray(plan_rounded_assign_from_scaling(K, u, v))
    assert (idx[:n_real] < m - 4).all()  # real rows avoid dead columns
    assert (idx[n_real:] < m - 4).all()  # padding falls back to live columns


def test_scaling_survives_wide_cost_ranges():
    """Per-row gauge shift: no row underflows even when range/eps >> 88.

    Regression: with a GLOBAL min shift, rows whose best entry sits far
    above the global min lost every kernel entry to exp-underflow and their
    scaling exploded — observed as 37% bucket overflow in the 10M-object
    hierarchical tier (random-normal features, std-normalized cost,
    eps=0.05).
    """
    import numpy as np

    from rio_tpu.ops import scaling_sinkhorn, sinkhorn

    key = jax.random.PRNGKey(11)
    n, m = 8192, 64
    # Heavy-tailed rows: some rows sit 20+ sigma from the global min.
    cost = jax.random.normal(key, (n, m)) + 30.0 * jax.random.uniform(
        jax.random.PRNGKey(12), (n, 1)
    )
    cost = cost / jnp.std(cost)
    mass, cap = jnp.ones((n,)), jnp.ones((m,))
    res = scaling_sinkhorn(cost, mass, cap, eps=0.05, n_iters=40)
    assert bool(jnp.isfinite(res.err)), "marginal error must be finite"
    assert float(res.err) < 0.05 * n  # marginals approximately matched
    ref = sinkhorn(cost, mass, cap, eps=0.05, n_iters=40)
    finite = jnp.isfinite(res.g) & jnp.isfinite(ref.g)
    assert float(jnp.max(jnp.abs(res.g[finite] - ref.g[finite]))) < 5e-2


def test_pallas_scaling_core_matches_xla_core():
    """pallas_scaling_core is a drop-in for scaling_core: same (u, v, K, shift).

    This is the contract the r5 promotion rides on (scaling_core_auto swaps
    one for the other based on backend/shape): u/v must match within dtype
    tolerance, and the returned K must be the UNPADDED kernel the rounding
    pass reuses."""
    from rio_tpu.ops.scaling import pallas_scaling_core, scaling_core

    cost, mass, cap = _problem(
        jax.random.PRNGKey(21), 96, 130, dead_nodes=3, padded_rows=5
    )
    u_x, v_x, K_x, sh_x = scaling_core(
        cost, mass, cap, eps=0.07, n_iters=20, kernel_dtype=jnp.float32
    )
    u_p, v_p, K_p, sh_p = pallas_scaling_core(
        cost, mass, cap, eps=0.07, n_iters=20,
        kernel_dtype=jnp.float32, block_rows=16, interpret=True,
    )
    assert K_p.shape == cost.shape  # unpadded, reusable by rounding
    np.testing.assert_allclose(np.asarray(u_p), np.asarray(u_x), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_x), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(K_p), np.asarray(K_x), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sh_p), np.asarray(sh_x), rtol=1e-6, atol=1e-6)


def test_scaling_core_auto_dispatch():
    """Off-TPU the dispatcher must pick XLA everywhere; the selection rule
    itself (bandwidth regime + block alignment) is pinned via the
    backend-independent arithmetic of scaling_impl_for."""
    from rio_tpu.ops.scaling import (
        _FUSED_MIN_ELEMS,
        scaling_core_auto,
        scaling_impl_for,
    )

    # On the CPU test mesh every shape resolves to XLA.
    assert scaling_impl_for(1 << 20, 1024) == "xla"
    # The auto path still solves correctly (it IS scaling_core here).
    cost, mass, cap = _problem(jax.random.PRNGKey(3), 64, 128)
    u, v, K, sh = scaling_core_auto(
        cost, mass, cap, eps=0.08, n_iters=15, kernel_dtype=jnp.float32
    )
    u_ref, v_ref, *_ = jax.jit(
        lambda c, a, b: __import__("rio_tpu.ops.scaling", fromlist=["scaling_core"]).scaling_core(
            c, a, b, eps=0.08, n_iters=15, kernel_dtype=jnp.float32
        )
    )(cost, mass, cap)
    np.testing.assert_allclose(np.asarray(u), np.asarray(u_ref), rtol=1e-6)
    # The selection arithmetic (what WOULD run on TPU) is shape-exact:
    # misaligned row counts and sub-VMEM problems must stay on XLA.
    assert (1 << 20) * 1024 >= _FUSED_MIN_ELEMS  # bench flagship shape qualifies
    assert (1 << 20) % 1024 == 0
    # Narrow-column exclusion (r5 TPU A/B: m=256 chained solve was 2.1x
    # SLOWER fused than XLA — 71.0 vs 33.3 ms at 1M objects): the selection
    # rule must keep sub-1024-column problems on XLA no matter how big n is.
    from rio_tpu.ops.scaling import _FUSED_MIN_COLS
    assert _FUSED_MIN_COLS >= 512
    assert (1 << 20) * 256 >= _FUSED_MIN_ELEMS  # big enough by elements...
    # ...yet excluded by column width on TPU (verified arithmetically here
    # since this suite runs on the CPU mesh).
