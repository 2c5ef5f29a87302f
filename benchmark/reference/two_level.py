"""The two-level re-plan in float64 NumPy, written from the docstrings of
``rio_tpu/parallel/hierarchical.py`` and ``JaxObjectPlacement._hierarchical_solve``.
Imports nothing of the program.

What the route promises, cell by cell. The directory's rows are cut, in
seating order, into ``devices x chunks`` cells; each cell is solved alone
against ``1 / cells`` of every node's capacity:

* coarse: the nodes form groups of ``GROUP_SIZE`` consecutive indices. A
  group's affinity for a row is that of its best live member, its capacity
  the sum of its live members'. The rows x groups cost (minus the affinity,
  over its standard deviation over live groups) goes through ``iters``
  Sinkhorn-Knopp iterations at ``eps`` from the seed potentials, rows then
  columns; the rows are rounded to groups and repaired to the groups'
  largest-remainder quotas of the cell's rows;
* fine: per group the same over its members, repaired to the members'
  largest-remainder quotas of the group's rows;
* a row's feature is its hashed identity plus ``move_cost`` times the
  embedding of the node that holds it now (the stay-put pull);
* the plan's per-node loads are the decision; which rows carry them is
  not, and the committed plan moves the fewest rows that reach those loads.

Departures from the program, each on purpose:

* float64 throughout, and the kernel ``exp(-cost / eps)`` exact: the
  program keeps it in bfloat16 and accumulates in float32;
* the rounding of rows to groups and nodes is not replayed (the program
  inverts each row's CDF at a quantile of its rank): :func:`assign` takes
  each row's best entry of the plan and repairs to the same quotas, which
  is why assignments are compared by cost and loads, never row by row;
* the module docstring of ``hierarchical.py`` said "capacity-weighted mean
  features" for a group; the code takes the best live member, and so does
  this (the docstring was corrected in PR 34);
* the hashed identities are DATA, made as the deployment defines them: a
  key's crc32 seeds ``jax.random.normal`` (the library, not the program),
  16 float32 draws a key. Everything after that is NumPy.
"""

import zlib

import numpy as np

GROUP_SIZE = 8
FEATURES = 16
BLOCK_ROWS = 65_536


def hashed(keys, dim: int = FEATURES) -> np.ndarray:
    """``(len(keys), dim)`` float64: the deployment's hashed identities."""
    import jax
    import jax.numpy as jnp

    out = np.empty((len(keys), dim), np.float64)
    draw = jax.jit(jax.vmap(lambda s: jax.random.normal(jax.random.PRNGKey(s), (dim,))))
    for a in range(0, len(keys), 4 * BLOCK_ROWS):
        seeds = np.fromiter(
            (zlib.crc32(k.encode()) & 0x7FFFFFFF for k in keys[a : a + 4 * BLOCK_ROWS]), np.uint32
        )
        out[a : a + seeds.shape[0]] = np.asarray(draw(jnp.asarray(seeds)), np.float64)
    return out


def row_features(keys, node_emb: np.ndarray, seats: np.ndarray, move_cost: float) -> np.ndarray:
    """Hashed identity plus the stay-put pull towards the current seat."""
    return hashed(keys) + move_cost * node_emb[np.asarray(seats, np.int64)]


def largest_remainder(expected: np.ndarray, n: int) -> np.ndarray:
    """Integer quotas summing to ``n``: floors of ``expected`` (which sum to
    about ``n``), then a unit each to the largest remainders."""
    expected = np.maximum(np.asarray(expected, np.float64), 0.0)
    q = np.floor(expected + 1e-9).astype(np.int64)
    order = np.argsort(-(expected - q), kind="stable")
    q[order[: max(0, n - int(q.sum()))]] += 1
    return q


def cell_loads(cap_alive: np.ndarray, rows: int, cells: int, group_size: int = GROUP_SIZE):
    """Per-node loads one cell of ``rows`` rows ends on: groups to their
    share of the cell, members to their share of the group. ``cap_alive`` is
    the whole directory's capacity vector (0 where not schedulable)."""
    cap = np.asarray(cap_alive, np.float64) / cells
    m = -(-cap.shape[0] // group_size) * group_size
    cap = np.pad(cap, (0, m - cap.shape[0])).reshape(-1, group_size)
    group_cap = cap.sum(axis=1)
    group_q = largest_remainder(group_cap / group_cap.sum() * rows, rows)
    loads = np.zeros(cap.shape, np.int64)
    for g in np.flatnonzero(group_q).tolist():
        loads[g] = largest_remainder(cap[g] / cap[g].sum() * group_q[g], int(group_q[g]))
    return loads.reshape(-1)[: np.asarray(cap_alive).shape[0]], group_q


def load_bounds(cap_alive, rows: int, cells: int, group_size: int = GROUP_SIZE):
    """``(lo, hi)`` per node for the whole directory, under any tie-break.
    Equal capacities make equal remainders, and which of the tied groups and
    nodes draw a cell's spare units is the solver's choice (the program takes
    the fuller, :func:`cell_loads` the lower index), cell by cell. A cell
    hands a group the floor or the ceiling of its share of the cell's rows,
    and a node the floor or the ceiling of its share of what its group got;
    every cell has the same rows and the same capacities."""
    if rows % cells:
        raise ValueError(f"{rows} rows do not divide into {cells} cells")
    cap = np.asarray(cap_alive, np.float64)
    m = -(-cap.shape[0] // group_size) * group_size
    cap = np.pad(cap, (0, m - cap.shape[0])).reshape(-1, group_size)
    group_cap = cap.sum(axis=1, keepdims=True)
    group_share = group_cap / group_cap.sum() * (rows // cells)
    within = cap / np.maximum(group_cap, 1e-300)
    lo = np.floor(np.floor(group_share + 1e-9) * within + 1e-9)
    hi = np.ceil(np.ceil(group_share - 1e-9) * within - 1e-9)
    n = np.asarray(cap_alive).shape[0]
    return (cells * lo.reshape(-1)[:n]).astype(np.int64), (cells * hi.reshape(-1)[:n]).astype(np.int64)


def rows_off_bounds(loads, lo, hi) -> int:
    """Rows above a node's upper bound plus rows short of a lower one."""
    loads = np.asarray(loads, np.int64)
    return int(np.maximum(loads - hi, 0).sum() + np.maximum(lo - loads, 0).sum())


def coarse_cost(feat: np.ndarray, node_feat: np.ndarray, cap_alive: np.ndarray,
                group_size: int = GROUP_SIZE) -> np.ndarray:
    """``(rows, groups)``: minus the best live member's affinity, over its
    standard deviation over live groups; 1e6 on a group with nobody live.
    Computed in blocks: the rows x nodes product is never whole in memory."""
    d, m = node_feat.shape
    groups = m // group_size
    live = (np.asarray(cap_alive, np.float64) > 0).reshape(groups, group_size)
    out = np.empty((feat.shape[0], groups), np.float64)
    for a in range(0, feat.shape[0], BLOCK_ROWS):
        aff = (feat[a : a + BLOCK_ROWS] @ node_feat).reshape(-1, groups, group_size)
        out[a : a + BLOCK_ROWS] = -np.where(live[None], aff, -np.inf).max(axis=2)
    live_group = live.any(axis=1)
    std = out[:, live_group].std()
    return np.where(live_group[None, :], out / max(std, 1e-6), 1e6)


def potentials(cost: np.ndarray, row_mass: np.ndarray, col_cap: np.ndarray, eps: float,
               iters: int, g_init=None):
    """``iters`` Sinkhorn-Knopp iterations in scaling form (rows, then
    columns), from ``v0 = exp(g_init / eps)`` (1 without a seed; a seed's
    entries that are not finite count as 0). Returns ``(f, g)``; -inf on a
    row without mass and a column without capacity."""
    a = np.asarray(row_mass, np.float64)
    b = np.asarray(col_cap, np.float64)
    a, b = a / max(a.sum(), 1e-30), b / max(b.sum(), 1e-30)
    shift = cost.min(axis=1, keepdims=True)
    kernel = np.exp(-(cost - shift) / eps)
    g0 = np.zeros_like(b) if g_init is None else np.asarray(g_init, np.float64)
    g0 = np.where(np.isfinite(g0), g0, 0.0)
    s = g0.max()
    v = np.exp(np.clip((g0 - s) / eps, -60.0, 0.0))
    u = np.zeros_like(a)
    for _ in range(iters):
        u = np.where(a > 0, a / np.maximum(kernel @ v, 1e-30), 0.0)
        v = np.where(b > 0, b / np.maximum(u @ kernel, 1e-30), 0.0)
    with np.errstate(divide="ignore"):
        f = np.where(u > 0, eps * np.log(np.maximum(u, 1e-300)) - s + shift[:, 0], -np.inf)
        g = np.where(v > 0, eps * np.log(np.maximum(v, 1e-300)) + s, -np.inf)
    return f, g


def cell_potentials(feat, node_feat, cap_alive, cells: int, eps: float, iters: int,
                    g_init=None, group_size: int = GROUP_SIZE) -> np.ndarray:
    """The coarse stage's group potentials for one cell's rows."""
    cap = np.asarray(cap_alive, np.float64) / cells
    cost = coarse_cost(feat, node_feat, cap, group_size)
    group_cap = cap.reshape(-1, group_size).sum(axis=1)
    return potentials(cost, np.ones(feat.shape[0]), group_cap, eps, iters, g_init)[1]


def _round_to_quotas(cost, f, g, eps, quotas) -> np.ndarray:
    """Each row to the best entry of its plan row; then every column keeps
    its cheapest rows up to its quota and the rest fill the columns still
    short, in column order."""
    logit = (f[:, None] + g[None, :] - cost) / eps
    out = np.argmax(np.where(np.isfinite(logit), logit, -np.inf), axis=1)
    kept = np.zeros(out.shape[0], bool)
    for j in range(cost.shape[1]):
        rows = np.flatnonzero(out == j)
        kept[rows[np.argsort(cost[rows, j], kind="stable")[: quotas[j]]]] = True
    have = np.bincount(out[kept], minlength=cost.shape[1])
    out[~kept] = np.repeat(np.arange(cost.shape[1]), np.maximum(quotas - have, 0))
    return out


def assign(feat, node_feat, cap_alive, cells: int, eps: float, iters: int, g_init=None,
           group_size: int = GROUP_SIZE):
    """One cell, whole: ``(assignment, coarse_g)``. For sizes a test holds;
    the node axis is a whole number of groups."""
    cap = np.asarray(cap_alive, np.float64) / cells
    n, m = feat.shape[0], node_feat.shape[1]
    groups = m // group_size
    cost = coarse_cost(feat, node_feat, cap, group_size)
    cap_g = cap.reshape(groups, group_size)
    f, g = potentials(cost, np.ones(n), cap_g.sum(axis=1), eps, iters, g_init)
    loads, group_q = cell_loads(cap_alive, n, cells, group_size)
    group = _round_to_quotas(cost, f, g, eps, group_q)
    # Each row against the members of its own group, on one scale for every
    # group (the program normalises the whole fine block at once; its block
    # holds padding rows of cost 0 besides, this one does not).
    own = -(feat @ node_feat).reshape(n, groups, group_size)[np.arange(n), group]
    own = own / max(own.std(), 1e-6)
    out = np.zeros(n, np.int64)
    for k in np.flatnonzero(group_q).tolist():
        rows = np.flatnonzero(group == k)
        fk, gk = potentials(own[rows], np.ones(rows.shape[0]), cap_g[k], eps, iters)
        quotas = loads.reshape(groups, group_size)[k]
        out[rows] = k * group_size + _round_to_quotas(own[rows], fk, gk, eps, quotas)
    return out, g


def transport_cost(feat, node_feat, assignment) -> float:
    """Sum over rows of minus the affinity for the node each was given."""
    return float(-np.einsum("id,di->i", feat, node_feat[:, np.asarray(assignment, np.int64)]).sum())


def least_moves(loads_before: np.ndarray, loads_after: np.ndarray) -> int:
    """Rows any plan must move to take the directory from one set of
    per-node loads to another: each node's net outflow."""
    d = np.asarray(loads_before, np.int64) - np.asarray(loads_after, np.int64)
    return int(np.maximum(d, 0).sum())
