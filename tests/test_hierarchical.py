"""Two-level OT placement: quality, liveness, overflow, and mesh sharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rio_tpu.ops.sinkhorn import route_sentinel_spill
from rio_tpu.parallel import make_mesh
from rio_tpu.parallel.hierarchical import (
    hierarchical_assign,
    sharded_hierarchical_assign,
)


def _features(key, n, d, m):
    k1, k2 = jax.random.split(key)
    obj = jax.random.normal(k1, (n, d), jnp.float32)
    node = jax.random.normal(k2, (d, m), jnp.float32) * 0.2
    return obj, node


def test_hierarchical_balances_and_avoids_dead_nodes():
    n, d, m, g = 2048, 16, 64, 8
    obj, node = _features(jax.random.PRNGKey(0), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[10].set(0.0).at[37].set(0.0)
    res = hierarchical_assign(obj, node, cap, alive, n_groups=g)
    a = np.asarray(res.assignment)
    assert a.min() >= 0 and a.max() < m
    # dead nodes attract nothing
    assert not np.any(np.isin(a, [10, 37]))
    # load balance: capacity-constrained OT keeps every live node near fair
    counts = np.bincount(a, minlength=m)
    fair = n / 62
    assert counts[np.setdiff1d(np.arange(m), [10, 37])].max() < 2.2 * fair
    assert int(res.overflow) == 0


def test_hierarchical_respects_affinity():
    """Objects aligned with a group's direction should land in that group."""
    n, d, m, g = 512, 8, 32, 4
    s = m // g
    key = jax.random.PRNGKey(1)
    # Groups have a shared feature direction (rack locality); nodes are
    # small perturbations of their group's direction.
    group_dirs = jax.random.normal(key, (g, d), jnp.float32)
    node = (
        jnp.repeat(group_dirs, s, axis=0)
        + 0.1 * jax.random.normal(jax.random.PRNGKey(7), (m, d))
    ).T  # (d, m)
    owner = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, m)
    obj = node.T[owner] * 3.0
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32)
    res = hierarchical_assign(obj, node, cap, alive, n_groups=g, eps=0.05)
    owner_group = np.asarray(owner) // s
    got_group = np.asarray(res.group)
    # Capacity quotas cap the match rate at the owner-group histogram's
    # overlap with uniform quotas; 0.6 is comfortably below that.
    assert np.mean(got_group == owner_group) > 0.6
    assert int(res.overflow) == 0


def test_hierarchical_capacity_weighting():
    n, d, m, g = 1024, 8, 16, 4
    obj, node = _features(jax.random.PRNGKey(3), n, d, m)
    cap = jnp.ones((m,), jnp.float32).at[0:4].set(3.0)  # group 0 is 3x
    alive = jnp.ones((m,), jnp.float32)
    res = hierarchical_assign(obj, node, cap, alive, n_groups=g)
    counts = np.bincount(np.asarray(res.group), minlength=g)
    # group 0 holds ~3x the objects of the others (3/(3+1+1+1) = 0.5)
    assert counts[0] > 0.38 * n


def test_hierarchical_overflow_fallback():
    """A tiny bucket forces overflow; fallbacks stay on live nodes."""
    n, d, m, g = 256, 8, 16, 4
    obj, node = _features(jax.random.PRNGKey(4), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[0].set(0.0)
    res = hierarchical_assign(obj, node, cap, alive, n_groups=g, bucket=16)
    assert int(res.overflow) > 0
    a = np.asarray(res.assignment)
    assert a.min() >= 0 and a.max() < m
    assert not np.any(a == 0)  # dead node excluded even on fallback


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_sharded_hierarchical_on_mesh():
    n, d, m, g = 4096, 16, 64, 8
    obj, node = _features(jax.random.PRNGKey(5), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[3].set(0.0)
    mesh = make_mesh(jax.devices()[:8])
    res = sharded_hierarchical_assign(mesh, obj, node, cap, alive, n_groups=g)
    a = np.asarray(res.assignment)
    assert a.shape == (n,)
    assert a.min() >= 0 and a.max() < m
    assert not np.any(a == 3)
    counts = np.bincount(a, minlength=m)
    assert counts[np.setdiff1d(np.arange(m), [3])].max() < 2.5 * (n / 63)


def test_fine_stage_sentinel_spill_routes_to_live_member():
    """r4 review: a real row seated on the padding-sentinel column (quota
    drift, or the repair's refill clip spilling into the last column) must
    NOT be clamped by take_along_axis onto member s-1 — it routes to the
    group's highest-capacity member, like the overflow fallback. The guard
    is the ONE shared implementation in ops.sinkhorn (also used by
    JaxObjectPlacement's bucket-shaped repair)."""
    s = 4  # group size; sentinel column index == s
    #          real rows on nodes, one real row spilled onto the sentinel,
    #          padding rows legitimately on the sentinel
    local = jnp.array([0, 2, s, s, s], jnp.int32)
    mass = jnp.array([1.0, 1.0, 1.0, 0.0, 0.0], jnp.float32)
    cap = jnp.array([1.0, 0.0, 1.0, 3.0], jnp.float32)  # member 1 dead, 3 biggest
    out = np.asarray(route_sentinel_spill(local, mass > 0, s, cap))
    assert out[0] == 0 and out[1] == 2  # untouched real rows
    assert out[2] == 3  # spilled real row -> argmax-capacity live member
    assert out[3] == s and out[4] == s  # padding keeps the sentinel


def test_hierarchical_dead_members_excluded_under_extreme_skew():
    """End-to-end guard exercise: groups whose capacity lives on ONE member
    (rest dead) stress the fine stage's quota/sentinel machinery; no real
    object may land on a dead node and every node stays in range."""
    n, d, m, g = 1024, 8, 32, 8
    obj, node = _features(jax.random.PRNGKey(11), n, d, m)
    s = m // g
    # In each group, only the first member is alive (capacity 4x to keep
    # group quotas equal); bucket sized for the skewed per-group share.
    alive = jnp.zeros((m,), jnp.float32).at[:: s].set(1.0)
    cap = jnp.ones((m,), jnp.float32) * 4.0
    res = hierarchical_assign(obj, node, cap, alive, n_groups=g, bucket=256)
    a = np.asarray(res.assignment)
    dead = np.asarray(alive) == 0.0
    assert not np.any(dead[a]), "object seated on a dead node"
    loads = np.bincount(a, minlength=m)
    assert loads[np.asarray(alive) > 0].sum() == n
    # Equal group capacities -> the g live members (one per group) carry
    # exact-quota fair shares of n, within largest-remainder rounding.
    live_loads = loads[np.asarray(alive) > 0]
    assert live_loads.max() - live_loads.min() <= 2, live_loads


@pytest.mark.slow
@pytest.mark.skipif(
    not os.environ.get("RIO_TPU_SCALE_MESH"),
    reason="opt-in (RIO_TPU_SCALE_MESH=1): 1M x 1024 on the 8-CPU mesh, minutes",
)
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_sharded_hierarchical_1m_x_1024_on_mesh():
    """r4 review, item 4: prove the sharding/memory math at the BASELINE
    row-5 node scale (1M objects x 1024 nodes, 32 groups) on the virtual
    mesh — four orders above the dryrun's phase-1 512 objects. Peak memory
    per shard stays O(N/8 x (G + S + d)) ~ 100 MB; a flat cost matrix
    would be 4 GB. Asserts the full quality contract: every object on a
    live node, zero overflow, exact-quota load spread, and the psum'd
    overflow counter consistent across shards."""
    n, d, m, g = 1_048_576, 16, 1024, 32
    obj, node = _features(jax.random.PRNGKey(21), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    dead = [5, 99, 640, 1023]
    alive = jnp.ones((m,), jnp.float32)
    for i in dead:
        alive = alive.at[i].set(0.0)
    mesh = make_mesh(jax.devices()[:8])
    res = sharded_hierarchical_assign(
        mesh, obj, node, cap, alive, n_groups=g, coarse_iters=16, fine_iters=16
    )
    jax.block_until_ready(res.assignment)
    a = np.asarray(res.assignment)
    assert a.shape == (n,)
    assert a.min() >= 0 and a.max() < m
    assert not np.any(np.isin(a, dead)), "object seated on a dead node"
    assert int(res.overflow) == 0
    loads = np.bincount(a, minlength=m)
    assert loads[dead].sum() == 0
    live_loads = loads[np.asarray(alive) > 0]
    fair = n / (m - len(dead))
    # Exact largest-remainder quotas per shard: global spread is bounded
    # by the summed per-shard roundings, far inside 10% of fair.
    assert live_loads.max() <= 1.1 * fair, (live_loads.max(), fair)
    assert live_loads.min() >= 0.9 * fair, (live_loads.min(), fair)


@pytest.mark.slow
@pytest.mark.skipif(
    os.environ.get("RIO_TPU_SCALE_MESH") != "full",
    reason="opt-in (RIO_TPU_SCALE_MESH=full): the FULL BASELINE row-5 shape, minutes + GBs",
)
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_sharded_hierarchical_10m_x_1024_full_row5_shape():
    """BASELINE row 5 VERBATIM (10,485,760 objects x 1024 nodes, 32 groups)
    through the sharded two-level solve on the 8-device mesh. A flat cost
    matrix at this shape is 40 GB — the factorized solve's per-shard
    working set is ~0.5 GB, which is the entire point. Same quality
    contract as the 1M tier."""
    import time

    n, d, m, g = 10_485_760, 16, 1024, 32
    obj, node = _features(jax.random.PRNGKey(23), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    dead = [7, 300, 512, 900]
    alive = jnp.ones((m,), jnp.float32)
    for i in dead:
        alive = alive.at[i].set(0.0)
    mesh = make_mesh(jax.devices()[:8])
    t0 = time.monotonic()
    res = sharded_hierarchical_assign(
        mesh, obj, node, cap, alive, n_groups=g, coarse_iters=16, fine_iters=16
    )
    jax.block_until_ready(res.assignment)
    wall = time.monotonic() - t0
    a = np.asarray(res.assignment)
    assert a.shape == (n,)
    assert not np.any(np.isin(a, dead))
    assert int(res.overflow) == 0
    loads = np.bincount(a, minlength=m)
    assert loads[dead].sum() == 0
    live_loads = loads[np.asarray(alive) > 0]
    fair = n / (m - len(dead))
    assert live_loads.max() <= 1.1 * fair and live_loads.min() >= 0.9 * fair
    print(f"\n10M x 1024 sharded hierarchical: {wall:.1f}s on the CPU mesh, "
          f"load spread {live_loads.min()}-{live_loads.max()} (fair {fair:.0f})")


def test_hierarchical_exact_node_quotas():
    """Both stages repair to exact largest-remainder quotas: every live
    node lands within 1 of its capacity share (was ±20% rounding noise)."""
    n, d, m, g = 8192, 8, 64, 8
    obj, node = _features(jax.random.PRNGKey(9), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32)
    res = hierarchical_assign(obj, node, cap, alive, n_groups=g)
    assert int(res.overflow) == 0
    loads = np.bincount(np.asarray(res.assignment), minlength=m)
    assert loads.max() - loads.min() <= 2  # group quota +-1, node quota +-1


def test_chunked_hierarchical_matches_flat_quality():
    """chunked_hierarchical_assign = the sharded design run temporally.

    Same contract the mesh version proves spatially: per-node loads exact
    to chunk granularity, dead nodes empty, zero overflow, and affinity
    quality on par with the flat solve (each chunk spreads over the same
    capacity proportions). This is the path that pins TPU compile cost to
    the chunk shape (v5e measured 599 s flat compile at 2.6M vs 50 s at
    the 655k chunk shape)."""
    from rio_tpu.parallel.hierarchical import chunked_hierarchical_assign

    n, d, m, g, chunks = 4096, 16, 64, 8, 4
    obj, node = _features(jax.random.PRNGKey(42), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[5].set(0.0).at[50].set(0.0)

    flat = hierarchical_assign(obj, node, cap, alive, n_groups=g)
    chunked = chunked_hierarchical_assign(
        obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    a = np.asarray(chunked.assignment)
    assert a.min() >= 0 and a.max() < m
    assert not np.any(np.isin(a, [5, 50]))
    assert int(chunked.overflow) == 0
    # Load exactness to chunk granularity: every live node within
    # n_chunks of the flat solve's (exact-quota) load.
    cf = np.bincount(np.asarray(flat.assignment), minlength=m)
    cc = np.bincount(a, minlength=m)
    assert np.abs(cc - cf).max() <= chunks
    # Affinity quality: mean assigned score within 2% of the flat solve.
    on = np.asarray(obj @ node)
    q_flat = on[np.arange(n), np.asarray(flat.assignment)].mean()
    q_chunk = on[np.arange(n), a].mean()
    spread = on.std()
    assert q_chunk >= q_flat - 0.02 * spread


def test_chunked_timed_twin_matches_lax_map_form_exactly():
    """The host-loop twin (``chunked_hierarchical_assign_timed``) calls
    the SAME jitted per-chunk solve the ``lax.map`` form runs, so its
    outputs are bit-identical — and it yields the per-chunk wall timings
    SolveStats banks (ISSUE 11 solver telemetry)."""
    from rio_tpu.parallel.hierarchical import (
        chunked_hierarchical_assign,
        chunked_hierarchical_assign_timed,
    )

    n, d, m, g, chunks = 256, 8, 8, 4, 4
    obj, node = _features(jax.random.PRNGKey(7), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[3].set(0.0)

    mapped = chunked_hierarchical_assign(
        obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    timed, chunk_ms = chunked_hierarchical_assign_timed(
        obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    assert np.array_equal(np.asarray(mapped.assignment),
                          np.asarray(timed.assignment))
    assert np.array_equal(np.asarray(mapped.group), np.asarray(timed.group))
    assert int(mapped.overflow) == int(timed.overflow)
    assert len(chunk_ms) == chunks
    assert all(ms > 0.0 for ms in chunk_ms)
    # The first chunk pays the one-time compile — the compile-vs-execute
    # signal the telemetry wants is visible in the timings themselves.
    assert chunk_ms[0] >= max(chunk_ms[1:])


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_mesh_chunked_matches_flat_and_chunked_quality():
    """mesh x chunk = the sharded design AND the chunked design composed.

    Each of the n_shards * n_chunks cells solves its slice against
    1/(n_shards * n_chunks) of every node's capacity, so the same
    per-slice-independence argument that makes each parent match the flat
    solve applies to the composition: per-node loads exact to CELL
    granularity, dead nodes empty, zero overflow, affinity quality on par
    with both the flat and the chunked-only solve."""
    from rio_tpu.parallel.hierarchical import (
        chunked_hierarchical_assign,
        mesh_chunked_hierarchical_assign,
    )

    n, d, m, g, chunks = 16384, 16, 64, 8, 2
    obj, node = _features(jax.random.PRNGKey(42), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[5].set(0.0).at[50].set(0.0)
    mesh = make_mesh(jax.devices()[:8])
    cells = 8 * chunks

    flat = hierarchical_assign(obj, node, cap, alive, n_groups=g)
    chunked = chunked_hierarchical_assign(
        obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    composed = mesh_chunked_hierarchical_assign(
        mesh, obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    a = np.asarray(composed.assignment)
    assert a.shape == (n,)
    assert a.min() >= 0 and a.max() < m
    assert not np.any(np.isin(a, [5, 50]))
    assert int(composed.overflow) == 0
    # Load exactness to cell granularity (each cell repairs to exact
    # largest-remainder quotas of its slice).
    cf = np.bincount(np.asarray(flat.assignment), minlength=m)
    cm = np.bincount(a, minlength=m)
    assert np.abs(cm - cf).max() <= cells
    # Quality within 2% of a cost-spread of BOTH parents (calibrated:
    # measured gaps 0.007/0.010 spreads at this shape).
    on = np.asarray(obj @ node)
    q_flat = on[np.arange(n), np.asarray(flat.assignment)].mean()
    q_chunk = on[np.arange(n), np.asarray(chunked.assignment)].mean()
    q_mesh = on[np.arange(n), a].mean()
    spread = on.std()
    assert q_mesh >= q_flat - 0.02 * spread, (q_mesh, q_flat, spread)
    assert q_mesh >= q_chunk - 0.02 * spread, (q_mesh, q_chunk, spread)
    # The composed solve returns REPLICATED finite coarse potentials (the
    # warm seed the placement layer persists into PlanState).
    cg = np.asarray(composed.coarse_g)
    assert cg.shape == (g,) and np.isfinite(cg).all()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_mesh_chunked_survives_wide_cost_ranges():
    """The per-row gauge shift must survive the composition: with raw
    affinities scaled 1000x (cost-range/eps >> 88, the regime where a
    GLOBAL shift underflows tail rows and the solve silently diverges —
    CLAUDE.md r3), the composed solve still balances, excludes the dead
    node, and matches flat quality."""
    from rio_tpu.parallel.hierarchical import mesh_chunked_hierarchical_assign

    n, d, m, g, chunks = 8192, 16, 32, 4, 2
    obj, node = _features(jax.random.PRNGKey(3), n, d, m)
    obj = obj * 1e3
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[7].set(0.0)
    mesh = make_mesh(jax.devices()[:8])

    res = mesh_chunked_hierarchical_assign(
        mesh, obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    a = np.asarray(res.assignment)
    assert not np.any(a == 7)
    assert int(res.overflow) == 0
    counts = np.bincount(a, minlength=m)
    live = np.setdiff1d(np.arange(m), [7])
    fair = n / len(live)
    assert counts[live].min() >= 0.9 * fair and counts[live].max() <= 1.1 * fair
    flat = hierarchical_assign(obj, node, cap, alive, n_groups=g)
    on = np.asarray(obj @ node)
    q_flat = on[np.arange(n), np.asarray(flat.assignment)].mean()
    q_mesh = on[np.arange(n), a].mean()
    assert q_mesh >= q_flat - 0.02 * on.std(), (q_mesh, q_flat)
    assert np.isfinite(np.asarray(res.coarse_g)).all()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_mesh_chunked_timed_twin_matches_lax_map_form_exactly():
    """The host-loop twin dispatches each chunk's mesh-wide slab through
    the SAME cell solve (identical single-step ``cap / (shards * chunks)``
    division, exact row->cell mapping), so assignment/group/overflow are
    bit-identical to the ``lax.map`` form — and the per-chunk wall timings
    expose the first-chunk compile for SolveStats."""
    from rio_tpu.parallel.hierarchical import (
        mesh_chunked_hierarchical_assign,
        mesh_chunked_hierarchical_assign_timed,
    )

    n, d, m, g, chunks = 2048, 8, 8, 4, 4
    obj, node = _features(jax.random.PRNGKey(7), n, d, m)
    cap = jnp.ones((m,), jnp.float32)
    alive = jnp.ones((m,), jnp.float32).at[3].set(0.0)
    mesh = make_mesh(jax.devices()[:8])

    mapped = mesh_chunked_hierarchical_assign(
        mesh, obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    timed, chunk_ms = mesh_chunked_hierarchical_assign_timed(
        mesh, obj, node, cap, alive, n_groups=g, n_chunks=chunks
    )
    assert np.array_equal(np.asarray(mapped.assignment),
                          np.asarray(timed.assignment))
    assert np.array_equal(np.asarray(mapped.group), np.asarray(timed.group))
    assert int(mapped.overflow) == int(timed.overflow)
    assert len(chunk_ms) == chunks
    assert all(ms > 0.0 for ms in chunk_ms)
    # First chunk pays the one-time cell compile.
    assert chunk_ms[0] >= max(chunk_ms[1:])
    # Warm-seed roundtrip: feeding the replicated potentials back in is
    # accepted by the same cached executable (no retrace on cold/warm flip)
    # and still yields a valid solve.
    timed2, chunk_ms2 = mesh_chunked_hierarchical_assign_timed(
        mesh, obj, node, cap, alive,
        n_groups=g, n_chunks=chunks, coarse_g_init=timed.coarse_g,
    )
    assert not np.any(np.asarray(timed2.assignment) == 3)
    assert int(timed2.overflow) == 0
    # Cached executable: the warm re-solve's first chunk pays no compile.
    assert chunk_ms2[0] < chunk_ms[0]


@pytest.mark.slow
@pytest.mark.skipif(
    not os.environ.get("RIO_TPU_SCALE_MESH"),
    reason="opt-in (RIO_TPU_SCALE_MESH=1): 10M x 1024 composed solve, minutes",
)
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
def test_mesh_chunked_10m_x_1024_compile_pinned_and_parity():
    """ISSUE 18 acceptance rung: 10,485,760 x 1024 through the composed
    mesh x chunk solve on the 8-virtual-device CPU mesh.

    The point of the composition is that compile cost pins to the CELL
    shape, not N: the 1M rung (8 shards x 2 chunks) and the 10M rung
    (8 shards x 20 chunks) use the SAME 65,536-row cell, so the 10M first
    chunk's compile must come in flat — within 1.5x of the 1M rung's
    (each rung compiles its own executable: the capacity scale constant
    differs, so this measures a genuine fresh compile at matched shape).
    Quality is checked against the chunked-only solve at the SAME N via a
    sampled transport-cost ratio (mean best-minus-assigned affinity
    regret) <= 1.05."""
    from rio_tpu.parallel.hierarchical import (
        chunked_hierarchical_assign,
        mesh_chunked_hierarchical_assign_timed,
    )

    d, m, g = 16, 1024, 32
    cell = 65_536
    mesh = make_mesh(jax.devices()[:8])
    cap = jnp.ones((m,), jnp.float32)
    dead = [7, 300, 512, 900]
    alive = jnp.ones((m,), jnp.float32)
    for i in dead:
        alive = alive.at[i].set(0.0)
    kw = dict(coarse_iters=16, fine_iters=16)

    # Rung A: 1M = 8 shards x 2 chunks x 65,536-row cells (cold compile).
    n1 = 8 * 2 * cell
    obj1, node = _features(jax.random.PRNGKey(23), n1, d, m)
    res1, ms1 = mesh_chunked_hierarchical_assign_timed(
        mesh, obj1, node, cap, alive, n_groups=g, n_chunks=2, **kw
    )
    assert int(res1.overflow) == 0

    # Rung B: 10M = 8 shards x 20 chunks x the SAME cell shape.
    n10 = 8 * 20 * cell
    obj10, _ = _features(jax.random.PRNGKey(29), n10, d, m)
    res10, ms10 = mesh_chunked_hierarchical_assign_timed(
        mesh, obj10, node, cap, alive, n_groups=g, n_chunks=20, **kw
    )
    a = np.asarray(res10.assignment)
    assert a.shape == (n10,)
    assert not np.any(np.isin(a, dead))
    assert int(res10.overflow) == 0
    loads = np.bincount(a, minlength=m)
    live_loads = loads[np.asarray(alive) > 0]
    fair = n10 / (m - len(dead))
    assert live_loads.max() <= 1.1 * fair and live_loads.min() >= 0.9 * fair
    # THE acceptance gate: first-chunk compile flat in N.
    assert ms10[0] <= 1.5 * ms1[0], (ms10[0], ms1[0])
    # Steady-state chunks never recompile.
    assert max(ms10[1:]) < ms10[0], (ms10[0], max(ms10[1:]))

    # Chunked-only comparator at matched N (single-chip dispatch shape:
    # 20 chunks of 524,288 rows = _HIER_CHUNK_ROWS).
    comp = chunked_hierarchical_assign(
        obj10, node, cap, alive, n_groups=g, n_chunks=20, **kw
    )
    ac = np.asarray(comp.assignment)
    # Sampled transport cost: mean regret (best live affinity minus the
    # assigned affinity) over a fixed 65,536-row sample — the (N x M)
    # affinity matrix at 10M x 1024 would be 40 GB, the sample is 256 MB.
    idx = np.arange(0, n10, n10 // 65_536)[:65_536]
    on_s = np.asarray(obj10[idx] @ node)
    on_s_live = np.where(np.asarray(alive)[None, :] > 0, on_s, -np.inf)
    best = on_s_live.max(axis=1)
    cost_mesh = float(np.mean(best - on_s[np.arange(len(idx)), a[idx]]))
    cost_chunk = float(np.mean(best - on_s[np.arange(len(idx)), ac[idx]]))
    assert cost_mesh <= 1.05 * cost_chunk, (cost_mesh, cost_chunk)
    print(f"\n10M x 1024 mesh x chunk: first-chunk {ms10[0]:.0f} ms "
          f"(1M rung {ms1[0]:.0f} ms), steady {np.median(ms10[1:]):.0f} ms, "
          f"transport-cost ratio {cost_mesh / cost_chunk:.4f}")
