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


# -- the hashed identities kept between re-plans (PR 36) ----------------------

N_KEPT = 3000


def _spy_on_the_feature_build(p, monkeypatch):
    """Every ``_build_obj_feat`` call's arguments and what it returned."""
    calls: list = []
    build = p._build_obj_feat

    def spy(*args):
        calls.append((args, build(*args)))
        return calls[-1][1]

    monkeypatch.setattr(p, "_build_obj_feat", spy)
    return calls, build


async def _nothing(p, ids):
    return N_KEPT, 0


async def _seats_changed(p, ids):
    for i in range(0, N_KEPT, 7):  # a hand-off's commit: the key keeps its place
        p._set_placement(str(ids[i]), (p._placements[str(ids[i])] + 1) % 13)
    return N_KEPT, 0


async def _keys_appended(p, ids):
    await p.assign_batch([ObjectId("R", f"late{i}") for i in range(90)])
    return N_KEPT, 90


async def _a_key_deleted(p, ids):
    await p.remove(ids[1234])
    return 0, N_KEPT - 1


async def _a_key_deleted_and_seated_again(p, ids):
    await p.remove(ids[1234])
    await p.assign_batch([ids[1234]])  # the same key, now last in the order
    return 0, N_KEPT


async def _a_member_cleaned(p, ids):
    victim = p._node_order[5]
    gone = sum(1 for i in p._placements.values() if i == 5)
    await p.clean_server(victim)
    return 0, N_KEPT - gone


@pytest.mark.parametrize("bf16", [False, True], ids=["float32", "RIO_TPU_HIER_FEAT_BF16"])
@pytest.mark.parametrize("between", [
    _nothing, _seats_changed, _keys_appended, _a_key_deleted,
    _a_key_deleted_and_seated_again, _a_member_cleaned,
], ids=lambda f: f.__name__.strip("_"))
async def test_a_second_solves_block_is_a_fresh_builds_to_the_last_bit(
    monkeypatch, between, bf16,
):
    """Rows whose key is where it was come from the kept block, the rest
    are hashed; the block handed to the mesh is byte for byte what a build
    from the key strings gives, and the counters say which rows were which."""
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    monkeypatch.setattr(jp, "_OBJ_FEAT_STREAM_ROWS", 700)  # several chunks, a ragged last
    if bf16:
        monkeypatch.setenv("RIO_TPU_HIER_FEAT_BF16", "1")
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10)
    ids = await _seated(p, N_KEPT, _nodes(16))
    calls, build = _spy_on_the_feature_build(p, monkeypatch)
    await p.rebalance(delta=False)
    assert p.stats.mode == "sinkhorn+hier_at_scale+mesh_chunk"
    assert calls[0][1][1:] == (0, N_KEPT)  # the first solve hashes every row
    g0 = p.place_gauges()
    want = await between(p, ids)
    await p.rebalance(delta=False)
    (args, (block, reused, hashed)), = calls[1:]
    assert (reused, hashed) == want and reused + hashed == len(p._placements)
    g1 = p.place_gauges()
    assert g1["rio.solve.features.rows_reused"] - g0["rio.solve.features.rows_reused"] == reused
    assert g1["rio.solve.features.rows_hashed"] - g0["rio.solve.features.rows_hashed"] == hashed
    assert block.dtype == jp._hier_feature_dtype() and block.dtype.itemsize == (2 if bf16 else 4)
    # The same snapshot, built from the key strings with nothing kept.
    kept_keys, kept_rows = p._kept_identities
    assert kept_keys == args[0] and kept_rows.shape == (len(args[0]), 16)
    assert not kept_rows.flags.writeable
    p._kept_identities = None
    fresh, fresh_reused, fresh_hashed = build(*args)
    assert (fresh_reused, fresh_hashed) == (0, len(args[0]))
    assert fresh.tobytes() == block.tobytes()
    assert p._kept_identities[1].tobytes() == kept_rows.tobytes()
    if between is _seats_changed:  # the pull differed, the identities did not
        assert calls[0][1][0].tobytes() != block.tobytes()


async def test_a_user_hook_is_called_on_every_key_at_every_solve_and_nothing_is_kept(
    monkeypatch,
):
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    seen: list = []

    def hook(keys):
        seen.extend(keys)
        return np.asarray(jp._hash_features(keys), np.float32)

    p = JaxObjectPlacement(mode="hierarchical", n_iters=10, obj_features=hook)
    ids = await _seated(p, N_KEPT, _nodes(16))
    for _ in range(2):
        seen.clear()
        await p.rebalance(delta=False)
        assert p.stats.mode == "hierarchical+mesh_chunk"
        assert seen == [str(i) for i in ids]
        assert p._kept_identities is None
    g = p.place_gauges()
    assert g["rio.solve.features.rows_reused"] == g["rio.solve.features.rows_hashed"] == 0


async def test_a_delta_s_subset_and_an_emptied_directory_leave_nothing_wrong_behind(
    monkeypatch,
):
    """A subset of the keys (what a two-level delta solves) neither reads
    nor replaces the kept block; a directory that emptied drops it."""
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10)
    ids = await _seated(p, N_KEPT, _nodes(16))
    await p.rebalance(delta=False)
    kept = p._kept_identities
    some = [str(i) for i in ids[100:400]]
    block, reused, hashed = p._build_obj_feat(some, 512, list(p._node_order), None, 0.0, None)
    assert (reused, hashed) == (0, 0) and p._kept_identities is kept
    assert block[:300].tobytes() == kept[1][100:400].tobytes()
    for i in ids:
        await p.remove(i)
    assert await p.rebalance(delta=False) == 0
    assert p._kept_identities is None


async def test_two_overlapping_re_plans_leave_a_consistent_block(monkeypatch):
    """``rebalance`` has no solve lock: two ``to_thread`` solves over two
    snapshots may finish in either order, and whichever swaps last leaves
    keys and rows that belong together."""
    monkeypatch.setattr(jp, "_FLAT_REBALANCE_MAX_ROWS", 1024)
    monkeypatch.setattr(jp, "_HIER_CHUNK_ROWS", 256)
    p = JaxObjectPlacement(mode="sinkhorn", n_iters=10)
    await _seated(p, N_KEPT, _nodes(16))

    async def grown():
        await asyncio.sleep(0)  # after the first solve's snapshot
        await p.assign_batch([ObjectId("R", f"late{i}") for i in range(50)])
        return await p.rebalance(delta=False)

    await asyncio.gather(p.rebalance(delta=False), grown())
    keys, rows = p._kept_identities
    assert len(keys) in (N_KEPT, N_KEPT + 50) and rows.shape == (len(keys), 16)
    assert keys == list(p._placements)[: len(keys)]
    assert rows.tobytes() == np.asarray(jp._hash_features(keys), np.float32).tobytes()
    await p.rebalance(delta=False)  # and the next solve is sound from either
    keys, rows = p._kept_identities
    assert keys == list(p._placements)
    assert rows.tobytes() == np.asarray(jp._hash_features(keys), np.float32).tobytes()


def test_many_solver_threads_over_many_snapshots_never_pair_rows_with_other_keys(monkeypatch):
    """More threads than cores, a short switch interval, snapshots that grow,
    shrink and reorder: whatever a thread reads or swaps, the rows it gets are
    its own keys' and the field's two halves belong together."""
    import sys
    import threading

    monkeypatch.setattr(jp, "_OBJ_FEAT_STREAM_ROWS", 128)
    p = JaxObjectPlacement(mode="sinkhorn")
    base = [f"R.{i}" for i in range(200)]
    want = np.asarray(jp._hash_features(base), np.float32)
    shapes = [base[:150], base, base[:120] + base[130:], base[::-1], base[:199]]
    picks = [list(range(150)), list(range(200)), [*range(120), *range(130, 200)],
             list(range(199, -1, -1)), list(range(199))]
    wrong: list = []

    def solver(seed):
        rng = np.random.default_rng(seed)
        for _ in range(8):
            k = int(rng.integers(len(shapes)))
            rows, reused = p._hashed_identities(list(shapes[k]))
            if rows.tobytes() != want[picks[k]].tobytes() or not 0 <= reused <= len(shapes[k]):
                wrong.append((k, reused))
            kept_keys, kept_rows = p._kept_identities
            if kept_rows.shape != (len(kept_keys), 16):
                wrong.append(("field", len(kept_keys), kept_rows.shape))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=solver, args=(s,)) for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong, wrong[:3]
