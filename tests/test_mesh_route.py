"""The four-chip directory (PR 34): the route of a full re-solve as one pure
rule, the mesh a default-wired directory builds for itself, the mesh x chunk
solve against the benchmark's plain reference, and its records.

Runs on the CPU backend's 8 virtual devices (conftest), as
``tests/test_hierarchical.py`` does.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import quotas, two_level
from rio_tpu import ObjectId, tracing
from rio_tpu.object_placement import jax_placement as jp
from rio_tpu.object_placement.jax_placement import JaxObjectPlacement, SolveRoute, solve_route
from rio_tpu.parallel import hierarchical as hier
from rio_tpu.parallel import make_mesh

M = 1_048_576


@pytest.mark.parametrize("mode, rows, devices, backend, priced, given, want", [
    # At or under what one chip's flat solve takes: today's routes, any host.
    ("sinkhorn", M, 1, "tpu", False, False, ("collapsed", "sinkhorn+collapsed", False)),
    ("sinkhorn", M, 4, "tpu", False, False, ("collapsed", "sinkhorn+collapsed", False)),
    ("auto", M, 4, "tpu", False, False, ("collapsed", "sinkhorn+collapsed", False)),
    ("scaling", M, 4, "tpu", False, False, ("collapsed", "scaling+collapsed", False)),
    ("sinkhorn", M, 4, "tpu", True, False, ("dense", "sinkhorn", False)),
    ("auto", M, 4, "cpu", False, False, ("greedy", "greedy", False)),
    ("greedy", 4 * M, 4, "tpu", False, False, ("greedy", "greedy", False)),
    ("hierarchical", M, 4, "tpu", False, False, ("hierarchical", "hierarchical", False)),
    # Past it on one device: the chunked two-level solve, as today.
    ("sinkhorn", 2 * M, 1, "tpu", False, False, ("hierarchical", "sinkhorn+hier_at_scale", False)),
    ("sinkhorn", 4 * M, 1, "tpu", True, False, ("hierarchical", "sinkhorn+hier_at_scale", False)),
    # Past it on four: sharded, by the TOTAL rows (4M / 4 is not past it).
    ("sinkhorn", 4 * M, 4, "tpu", False, False, ("hierarchical", "sinkhorn+hier_at_scale", True)),
    ("auto", 4 * M, 4, "tpu", False, False, ("hierarchical", "sinkhorn+hier_at_scale", True)),
    ("sinkhorn", 2 * M, 4, "cpu", False, False, ("hierarchical", "sinkhorn+hier_at_scale", True)),
    ("hierarchical", 4 * M, 4, "tpu", False, False, ("hierarchical", "hierarchical", True)),
    # A mesh that was given wins: it shards whatever it can, and 4M rows on
    # it never reach the dense sharded branch.
    ("sinkhorn", M, 4, "tpu", False, True, ("dense", "sinkhorn", True)),
    ("sinkhorn", 4 * M, 4, "tpu", False, True, ("hierarchical", "sinkhorn+hier_at_scale", True)),
    ("hierarchical", 4096, 4, "tpu", False, True, ("hierarchical", "hierarchical", True)),
])
def test_the_route_of_a_full_re_solve(mode, rows, devices, backend, priced, given, want):
    assert solve_route(mode, rows, devices, backend, priced, given) == SolveRoute(*want)


async def _seated(p, n, nodes):
    p.sync_members(nodes)
    ids = [ObjectId("R", str(i)) for i in range(n)]
    await p.assign_batch(ids)
    return ids


def _nodes(m):
    return [f"10.77.{i // 250}.{i % 250 + 1}:7000" for i in range(m)]


async def test_a_default_wired_directory_builds_its_mesh_at_the_first_solve_past_the_bound(
    monkeypatch,
):
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10)
    assert p._mesh is None and p._local_mesh is None  # no backend touch in the constructor
    await _seated(p, 3000, _nodes(16))
    await p.rebalance(delta=False)
    assert p.stats.mode == "sinkhorn+collapsed" and p.stats.devices == 0
    assert p._local_mesh is None  # one chip's flat solve takes it: no mesh
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    await p.rebalance(delta=False)
    assert p.stats.mode == "sinkhorn+hier_at_scale+mesh_chunk"
    assert p.stats.devices == jax.local_device_count() == 8 and p.stats.chunks == 2
    assert p._local_mesh is not None and p._local_mesh.devices.size == 8


async def test_a_mesh_that_is_given_wins_over_the_local_devices(monkeypatch):
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    mesh = make_mesh(jax.devices()[:2])
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10, mesh=mesh)
    await _seated(p, 3000, _nodes(16))
    await p.rebalance(delta=False)
    assert p.stats.mode == "sinkhorn+hier_at_scale+mesh_chunk"
    assert p.stats.devices == 2 and p.stats.chunks == 8 and p._local_mesh is None


# -- mesh x chunk against the plain reference ---------------------------------

ROWS, NODES, FEAT, SHARDS, CHUNKS = 8192, 64, 16, 4, 2
CELLS = SHARDS * CHUNKS
SOLVER = dict(eps=0.05, coarse_iters=30, fine_iters=30)


@pytest.fixture(scope="module")
def solved():
    """One seeded mesh x chunk solve (4 devices x 2 chunks) and its inputs."""
    rng = np.random.default_rng(34)
    cap = np.ones(NODES, np.float32)
    cap[[3, 17, 40]] = 0.0  # members that are down
    cap[[5, 6]] = 0.875, 0.5  # live servers a load monitor derated
    node_feat = rng.standard_normal((FEAT, NODES)).astype(np.float32)
    cur = rng.choice(np.flatnonzero(cap > 0), ROWS)
    feat = (rng.standard_normal((ROWS, FEAT)) + 0.5 * node_feat.T[cur]).astype(np.float32)
    seed = (rng.standard_normal(NODES // 8) * 0.01).astype(np.float32)
    res, chunk_ms = hier.mesh_chunked_hierarchical_assign_timed(
        make_mesh(jax.devices()[:SHARDS]), feat, jnp.asarray(node_feat), jnp.asarray(cap),
        jnp.asarray((cap > 0).astype(np.float32)), n_groups=NODES // 8, n_chunks=CHUNKS,
        bucket=256, coarse_g_init=jnp.asarray(seed), **SOLVER,
    )
    return dict(cap=cap, node_feat=node_feat.astype(np.float64), seed=seed,
                cells=feat.astype(np.float64).reshape(SHARDS, CHUNKS, ROWS // CELLS, FEAT),
                assignment=np.asarray(res.assignment), coarse_g=np.asarray(res.coarse_g),
                overflow=int(res.overflow), chunk_ms=chunk_ms)


def test_mesh_chunk_loads_lie_within_the_references_bounds(solved):
    loads = np.bincount(solved["assignment"], minlength=NODES)
    lo, hi = two_level.load_bounds(solved["cap"], ROWS, CELLS)
    assert solved["overflow"] == 0 and loads.sum() == ROWS and len(solved["chunk_ms"]) == CHUNKS
    assert two_level.rows_off_bounds(loads, lo, hi) == 0
    assert loads[solved["cap"] == 0].sum() == 0


def test_the_cells_shares_add_up_to_the_whole_directorys(solved):
    """Each of the 8 cells is solved against an eighth of every node's
    capacity; what they give, added up, is what the uncut reference gives
    for the whole directory, to the two roundings a cell."""
    per_cell = solved["assignment"].reshape(CELLS, ROWS // CELLS)
    loads = sum(np.bincount(rows, minlength=NODES) for rows in per_cell)
    whole = quotas.largest_remainder(solved["cap"], ROWS)
    assert np.abs(loads - whole).max() <= 2 * CELLS - 1
    for rows in per_cell:  # and a cell alone is the reference's cell, to a tied unit
        want, _ = two_level.cell_loads(solved["cap"], ROWS // CELLS, CELLS)
        assert np.abs(np.bincount(rows, minlength=NODES) - want).max() <= 2


def test_mesh_chunk_coarse_potentials_are_the_references(solved):
    """The committed potentials are the mean over the devices of each one's
    LAST cell, from the seed; the program's kernel is bfloat16."""
    ref = np.mean([
        two_level.cell_potentials(solved["cells"][s, CHUNKS - 1], solved["node_feat"],
                                  solved["cap"], CELLS, 0.05, 30, solved["seed"])
        for s in range(SHARDS)
    ], axis=0)
    assert np.abs(solved["coarse_g"] - ref).max() < 2e-3
    cold = two_level.cell_potentials(solved["cells"][0, 0], solved["node_feat"], solved["cap"],
                                     CELLS, 0.05, 30, None)
    assert np.abs(solved["coarse_g"] - cold).max() > 2e-2  # another cell, no seed: far off


def test_mesh_chunk_assignment_costs_what_the_references_does(solved):
    nf, rows = solved["node_feat"], ROWS // CELLS
    for s, c in ((0, 0), (3, 1)):
        feat = solved["cells"][s, c]
        ours = solved["assignment"].reshape(SHARDS, CHUNKS, rows)[s, c]
        ref, _ = two_level.assign(feat, nf, solved["cap"], CELLS, 0.05, 30, solved["seed"])
        want, _ = two_level.cell_loads(solved["cap"], rows, CELLS)
        assert (np.bincount(ref, minlength=NODES) == want).all()
        cost, ref_cost = (two_level.transport_cost(feat, nf, a) for a in (ours, ref))
        # Costs are negative (minus the affinity): within a tenth of the
        # reference's, whose rounding takes each row's best entry and ours a
        # quantile of the row's plan.
        assert cost <= ref_cost + 0.1 * abs(ref_cost), (cost, ref_cost)


# -- the served path: moves, stage records, counters --------------------------


async def test_a_second_re_plan_moves_under_a_hundredth_and_only_through_the_sink(monkeypatch):
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    p = JaxObjectPlacement(mode="sinkhorn", mesh=make_mesh(jax.devices()[:4]))
    nodes = _nodes(64)
    ids = await _seated(p, 8192, nodes)
    p.sync_members(nodes[:-3])  # three members down: the first re-plan has work
    sunk: list = []

    async def sink(moves):
        sunk.extend(moves)
        for key, _src, dst in moves:  # what a hand-off's commit does
            p._set_placement(key, p._node_index(dst))
        return len(moves)

    tracing.clear_stages()
    g0 = p.place_gauges()
    first = await p.rebalance(delta=False, move_sink=sink)
    before = list(p._placements.values())
    assert p.stats.mode == "sinkhorn+hier_at_scale+mesh_chunk"
    assert (p.stats.devices, p.stats.chunks) == (4, 8)
    assert first == len(sunk) >= 3 * 8192 // 64 - 8  # the leavers' rows, and few more
    assert first <= 1.25 * 3 * 8192 / 64
    second = await p.rebalance(delta=False, move_sink=sink)
    assert second == len(sunk) - first <= 0.01 * len(ids)
    changed = sum(a != b for a, b in zip(before, p._placements.values()))
    assert changed == second  # no row changed seat but through the sink
    on_dead = sum(p._node_order[i] in nodes[-3:] for i in p._placements.values())
    assert on_dead == 0
    # The stage records of a mesh solve, under solve.device, one call id.
    recs = tracing.stage_log()
    for call in {r[4] for r in recs if r[0] == "solve.full"}:
        mine = [r for r in recs if r[4] == call]
        names = [r[0] for r in mine]
        assert names.count("solve.mesh.inputs") == names.count("solve.mesh.cells") == 8
        assert names.count("solve.mesh.gather") == names.count("solve.features") == 1
        device = next(r for r in mine if r[0] == "solve.device")
        for r in mine:
            if r[0].startswith("solve.mesh."):
                assert r[3] == "solve.device" and device[1] <= r[1] <= r[2] <= device[2]
    g1 = p.place_gauges()
    assert g1["rio.solve.mesh.solves"] - g0["rio.solve.mesh.solves"] == 2
    assert g1["rio.solve.mesh.devices"] == 4
    assert g1["rio.solve.mesh.cells"] - g0["rio.solve.mesh.cells"] == 2 * 4 * 8


async def test_a_solve_one_chip_takes_counts_no_mesh_solve():
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10)
    await _seated(p, 600, _nodes(8))
    await p.rebalance(delta=False)
    g = p.place_gauges()
    assert g["rio.solve.mesh.solves"] == g["rio.solve.mesh.cells"] == g["rio.solve.mesh.devices"] == 0


def test_the_two_level_body_reaches_its_solve_steps_through_their_modules(monkeypatch):
    """Whoever replaces a step in its module (the benchmark's bfloat16
    control) lowers the two-level route too: the body looks the steps up
    when it is traced, not when ``hierarchical.py`` is imported."""
    from importlib import import_module

    scaling, sinkhorn = (import_module(f"rio_tpu.ops.{m}") for m in ("scaling", "sinkhorn"))
    seen: list = []

    def spy(mod, name):
        orig = getattr(mod, name)

        def wrapped(*a, **kw):
            seen.append(name)
            return orig(*a, **kw)

        monkeypatch.setattr(mod, name, wrapped)

    spy(scaling, "scaling_sinkhorn")
    spy(sinkhorn, "plan_rounded_assign")
    spy(sinkhorn, "exact_quota_repair")
    rng = np.random.default_rng(0)
    # A shape no other test of this process traces: the jit cache is by shape.
    res = hier.hierarchical_assign(
        rng.standard_normal((328, 16)).astype(np.float32),
        rng.standard_normal((16, 24)).astype(np.float32),
        jnp.ones(24), jnp.ones(24), n_groups=3, bucket=128, coarse_iters=5, fine_iters=5,
    )
    assert int(res.overflow) == 0
    assert {"scaling_sinkhorn", "plan_rounded_assign", "exact_quota_repair"} <= set(seen)
    assert seen.count("scaling_sinkhorn") == 2  # coarse, and the fine stage's vmapped body


def test_fewest_moves_takes_millions_of_movers_in_numpy():
    """A two-level plan hands in most of the directory as movers; the pass
    is vectorised (it runs beside the servers' loop)."""
    rng = np.random.default_rng(1)
    n, m = 1 << 20, 1024
    cur = rng.integers(0, m, n).astype(np.int32)
    plan = rng.permutation(cur)  # the same loads, dealt again
    out = jp._cancel_transit(plan, cur)
    assert (out == cur).all()
    plan[: n // 64] = 7  # and a real shift of load onto one node
    out = jp._cancel_transit(plan, cur)
    assert (np.bincount(out, minlength=m) == np.bincount(plan, minlength=m)).all()
    net = np.bincount(plan, minlength=m) - np.bincount(cur, minlength=m)
    assert (out != cur).sum() == np.maximum(net, 0).sum()


def _dot_generals(jaxpr):
    """Every ``dot_general`` equation of a jaxpr, nested bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _dot_generals(inner)


def test_the_affinities_are_contracted_in_float32_on_every_backend():
    """A TPU's default would round both operands of a float32 contraction to
    bfloat16; the two-level body asks for the highest precision by name (a
    CPU run cannot show the difference, the traced program can)."""
    rng = np.random.default_rng(2)
    closed = jax.make_jaxpr(
        lambda f, n, c: hier._hierarchical_assign_impl(f, n, c, c, n_groups=2, bucket=64)
    )(rng.standard_normal((96, 16)).astype(np.float32),
      rng.standard_normal((16, 16)).astype(np.float32), jnp.ones(16))
    # The contractions over the 16 features: rows x group members (coarse,
    # one group a step) and buckets x group members (fine).
    affinities = [e for e in _dot_generals(closed.jaxpr)
                  if e.outvars[0].aval.shape in ((96, 8), (2, 64, 8))]
    assert len(affinities) == 2
    for eqn in affinities:
        assert "HIGHEST" in str(eqn.params["precision"]), eqn
