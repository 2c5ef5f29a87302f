"""JaxObjectPlacement: the TPU-accelerated placement provider.

Implements the reference's ``ObjectPlacement`` trait
(``rio-rs/src/object_placement/mod.rs:39-56``) — so it drops into
``Service.get_or_create_placement`` unchanged — but replaces the per-request
SQL round trip (``rio-rs/src/service.rs:220``, named the bottleneck in
``BASELINE.md``) with:

- a **host-mirrored directory** (dict) answering ``lookup`` in O(1) with no
  I/O — the fast read path the router consumes;
- a **device-resident solve**: batched assignment of unplaced objects via
  cached node potentials (one cost row + one argmin per object,
  :func:`rio_tpu.ops.assignment.assign_from_potentials`), refreshed by full
  Sinkhorn/greedy re-solves (:func:`rio_tpu.ops.sinkhorn.sinkhorn_assign`,
  sharded across a mesh via :mod:`rio_tpu.parallel` at scale);
- **epoch versioning** for consistency: every mutation bumps an epoch; a
  re-solve snapshots the epoch and its result is discarded if the directory
  moved underneath it (single-writer semantics replacing the reference's
  reliance on SQL upsert atomicity, ``object_placement/sqlite.rs:72-85``).
  The snapshot itself is a cut at that one epoch: a large directory is read
  in slices with a turn of the event loop between two, and a writer that
  lands between them (the epoch or the row count moved) restarts the read
  at the new epoch (:meth:`JaxObjectPlacement._snapshot`).

Liveness flows in from gossip (``MembershipStorage``) via
:meth:`JaxObjectPlacement.sync_members`, mirroring how the reference's
service checks ``is_active`` before honoring a placement
(``service.rs:213-238``).
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import itertools
import logging
import os
import threading
import time
from dataclasses import dataclass, field, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import NoSchedulableCapacity
from ..registry import ObjectId
from ..tracing import stage, stage_between, stage_since
from ..utils.jaxenv import compile_cache_dir
from ..ops import (
    build_cost_matrix,
    greedy_balanced_assign,
    integer_fair_quotas,
    plan_rounded_assign,
    residual_capacity_assign,
    scaling_sinkhorn,
    sinkhorn,
)
from . import ObjectPlacement, ObjectPlacementItem, sanitize_standby_row

log = logging.getLogger(__name__)

_FEAT_DIM = 16  # hashed-identity feature width for the hierarchical mode


def _hash_features(keys: list[str], dim: int = _FEAT_DIM) -> jax.Array:
    """Stable pseudo-random feature per key (identity/cache-warmth proxy).

    crc32 of the key seeds a per-key PRNG; the feature is deterministic
    across processes, so affinity survives restarts without storage.
    """
    import zlib

    seeds = np.asarray([zlib.crc32(k.encode()) & 0x7FFFFFFF for k in keys], np.uint32)
    return jax.vmap(lambda s: jax.random.normal(jax.random.PRNGKey(s), (dim,)))(
        jnp.asarray(seeds)
    )


class AffinityTracker:
    """Turns observed traffic into placement features for hierarchical mode.

    The two-level solver scores ``affinity[i, j] = obj_feat[i] @ node_feat
    [:, j]``; this tracker makes that product mean something: each node gets
    a stable embedding, and each object's feature is a request-weighted EMA
    of the embeddings of nodes that served it (cache warmth / state
    locality), so the OT objective pulls an object toward where its state
    is hot — while the capacity marginals still enforce balance. The
    reference has no counterpart (placement there is a random pick,
    ``client/mod.rs:255-262``); without it the hierarchical mode's
    affinity term carries no locality signal.

    Wire it up::

        tracker = AffinityTracker()
        placement = JaxObjectPlacement(
            mode="hierarchical",
            obj_features=tracker.obj_features,
            node_features=tracker.node_features,
        )
        ...
        tracker.observe(str(object_id), serving_address, weight=1.0)
    """

    def __init__(
        self,
        dim: int = _FEAT_DIM,
        stickiness: float = 0.25,
        max_objects: int = 262_144,
    ) -> None:
        self.dim = dim
        # Hard bound on per-object state (_obj warmth vectors, rate EMAs,
        # state-bytes records): a high-cardinality workload — millions of
        # one-shot actor ids — would otherwise grow the tracker without
        # limit. fold_rates() enforces it by evicting the COLDEST entries
        # (lowest folded req/sec; unknown rate counts as 0) down to the
        # cap; the hottest objects, the only ones whose warmth can change
        # a placement decision, always survive. ``evictions`` counts
        # dropped entries for telemetry.
        self.max_objects = int(max_objects)
        self.evictions = 0
        # EMA coefficient toward the serving node's embedding per unit
        # weight; 0.0 disables learning.  The default keeps MULTI-node
        # warmth: with interleaved traffic the feature converges to the
        # traffic-share mix of the serving nodes' embeddings (a 3:1 split
        # leaves a clearly detectable secondary component), while a high
        # value (~1) degenerates to last-server-wins and erases every
        # warm replica the moment traffic touches the primary — measured
        # to destroy the churn-failover payoff in
        # ``tests/test_affinity_payoff.py``.
        self.stickiness = stickiness
        self._obj: dict[str, np.ndarray] = {}
        self._node_cache: dict[str, np.ndarray] = {}
        # Measured per-object cost features (rio_tpu/load): request counts
        # accumulated since the last fold_rates() tick, the folded req/sec
        # EMA, and the last observed migration-snapshot size. move_weights()
        # turns these into per-object move prices for the solver; the
        # LoadMonitor's sampling loop drives fold_rates(). All maps follow
        # the same atomic-swap discipline as _obj (solver thread reads
        # concurrently).
        self._req_window: dict[str, float] = {}
        self._rates: dict[str, float] = {}
        self._state_bytes: dict[str, float] = {}
        self._rate_fold_t = time.monotonic()

    def _node_vec(self, address: str) -> np.ndarray:
        vec = self._node_cache.get(address)
        if vec is None:
            vec = np.asarray(_hash_features([address], self.dim))[0]
            vec = vec / max(float(np.linalg.norm(vec)), 1e-9)
            self._node_cache[address] = vec
        return vec

    def observe(self, key: str, node_address: str, weight: float = 1.0) -> None:
        """Record that ``key`` was served by ``node_address``.

        ``weight`` scales the pull (e.g. request count since last observe,
        or bytes of state touched).  Alpha is capped below 1 so a single
        heavy observation can never fully erase accumulated warmth."""
        self._req_window[key] = self._req_window.get(key, 0.0) + max(0.0, weight)
        alpha = min(0.95, self.stickiness * weight)
        if alpha <= 0.0:
            return
        target = self._node_vec(node_address)
        cur = self._obj.get(key)
        if cur is None and len(self._obj) >= 2 * self.max_objects:
            # Backstop when no LoadMonitor drives fold_rates(): force a
            # fold (which evicts down to max_objects) before admitting a
            # new key, so the tracker never exceeds 2x its cap.
            self.fold_rates(min_dt=0.0)
            cur = self._obj.get(key)
        if cur is None:
            # Cold object: blend from the same weak hashed-identity base
            # obj_features() would have used, so a low-weight stray request
            # nudges rather than fully re-homes it.
            cur = np.asarray(_hash_features([key], self.dim), np.float32)[0] * 0.1
        # Atomic swap (never mutate in place): the solver thread reads
        # self._obj concurrently via obj_features() during a rebalance.
        new = (1.0 - alpha) * cur + alpha * target
        norm = float(np.linalg.norm(new))
        if norm > 1e-9:
            new = new / norm
        self._obj[key] = new

    def obj_features(self, keys: list[str]) -> np.ndarray:
        """(n, dim) features: learned EMA, hashed-identity for cold objects."""
        out = np.asarray(_hash_features(keys, self.dim), np.float32) * 0.1
        for i, k in enumerate(keys):
            vec = self._obj.get(k)
            if vec is not None:
                out[i] = vec
        return out

    def node_features(self, addresses: list[str]) -> np.ndarray:
        """(m, dim) embeddings matching what ``observe`` pulled toward."""
        if not addresses:
            return np.zeros((0, self.dim), np.float32)
        return np.stack([self._node_vec(a) for a in addresses]).astype(np.float32)

    # ------------------------------------------- measured cost features
    def fold_rates(self, beta: float = 0.3, min_dt: float = 0.05) -> None:
        """Fold the since-last-tick request window into per-object req/sec
        EMAs (driven by the LoadMonitor's sampling loop). Builds fresh
        dicts and swaps — never mutates in place, the solver thread reads
        move_weights() concurrently."""
        now = time.monotonic()
        dt = now - self._rate_fold_t
        if dt < min_dt:
            return
        self._rate_fold_t = now
        window, self._req_window = self._req_window, {}
        rates: dict[str, float] = {}
        for k, old in self._rates.items():
            new = (1.0 - beta) * old + beta * (window.pop(k, 0.0) / dt)
            if new > 1e-6:  # drop cooled-off objects: the map stays bounded
                rates[k] = new
        for k, cnt in window.items():
            rates[k] = beta * (cnt / dt)
        self._rates = rates
        # Enforce the max_objects bound on every per-object map. Build
        # fresh dicts and swap (solver thread reads concurrently); evict
        # coldest-by-rate first so the warmth that matters survives.
        for name in ("_obj", "_state_bytes"):
            cur = getattr(self, name)
            over = len(cur) - self.max_objects
            if over <= 0:
                continue
            doomed = sorted(cur, key=lambda k: rates.get(k, 0.0))[:over]
            kept = dict(cur)
            for k in doomed:
                del kept[k]
            setattr(self, name, kept)
            self.evictions += over
        if len(rates) > self.max_objects:
            over = len(rates) - self.max_objects
            doomed = sorted(rates, key=rates.get)[:over]
            kept_r = dict(rates)
            for k in doomed:
                del kept_r[k]
            self._rates = kept_r
            self.evictions += over

    def total_rate(self) -> float:
        return float(sum(self._rates.values()))

    def object_rates(self) -> dict[str, float]:
        """Snapshot of the folded per-object req/sec EMAs.

        Keys are observer keys (``"{type_name}.{id}"`` == ``str(ObjectId)``).
        The read-scale hotness detector consumes this; a plain dict copy of
        the atomically-swapped map, safe against the concurrent fold."""
        return dict(self._rates)

    def note_state_bytes(self, key: str, nbytes: int) -> None:
        """Record the object's last migration-snapshot size (its state
        weight). Called by the migration manager at handoff time."""
        self._state_bytes[key] = float(max(0, nbytes))

    def move_weights(
        self,
        keys: list[str],
        *,
        rate_scale: float = 10.0,
        bytes_scale: float = 1 << 20,
        max_weight: float = 16.0,
    ) -> np.ndarray:
        """(n,) per-object move prices for the solver's stay-put discount.

        ``1.0`` for a cold object, growing with measured request rate
        (cache warmth lost on a move) and snapshot size (bytes that must
        cross the wire), capped so one pathological actor can't dominate
        the objective. ``JaxObjectPlacement`` consumes this via its
        ``object_costs`` hook."""
        rates, sizes = self._rates, self._state_bytes  # snapshot refs
        out = np.ones((len(keys),), np.float32)
        for i, k in enumerate(keys):
            w = 1.0 + rates.get(k, 0.0) / rate_scale + sizes.get(k, 0.0) / bytes_scale
            out[i] = min(max_weight, w)
        return out


# Hierarchical solves chunk the object axis above this row count (power
# of two, so it divides every larger po2 bucket): the TPU backend's
# compile time is superlinear in the flat row count while the chunked
# lax.map body compiles once at the chunk shape. Devices divide the rows
# first, chunks divide each device's slice (parallel/hierarchical.py
# mesh_chunked_hierarchical_assign); on a single chip the bound is the
# lax.map chunk's. One 524,288-row chunk compiles cold in 44 s on a v5e
# under jax 0.9.0 (chip_smoke.py, PR 21; 46 s for the mesh x chunk cell on
# four chips).
# RIO_TPU_HIER_CHUNK_ROWS overrides (po2; CI smokes use a tiny value to
# exercise the composed dispatch at test shapes in seconds).
_HIER_CHUNK_ROWS = int(os.environ.get("RIO_TPU_HIER_CHUNK_ROWS") or 524_288)

# Flat OT rebalances of more than this many padded rows IN THE WHOLE
# DIRECTORY route through the two-level solve (solve_route): the TPU
# backend's compile time for a flat pipeline is superlinear in its row
# count (neither 10.5M nor 4.2M rows finished a 900 s compile budget, v5e,
# r5 capture of 2026-07-31, not re-measured; 1,048,576 rows compile cold in
# 150 s, chip_smoke.py, PR 21), and the dense sharded solve a mesh would
# run instead builds the whole rows x nodes cost on the host first (17 GB
# at 4,194,304 x 1,024). The rule counts total rows, not rows a device,
# for that reason: dividing by the devices sent 4,194,304 rows on four
# chips to the dense branch. The threshold is the largest flat bucket
# proven on hardware. RIO_TPU_FLAT_REBALANCE_MAX_ROWS overrides (CI smoke
# knob).
_FLAT_REBALANCE_MAX_ROWS = int(
    os.environ.get("RIO_TPU_FLAT_REBALANCE_MAX_ROWS") or 1_048_576
)


class SolveRoute(NamedTuple):
    """What a full re-solve runs as (:func:`solve_route`)."""

    path: str  # "hierarchical" | "collapsed" | "dense" | "greedy"
    # ``SolveStats.mode``; a two-level solve that is sharded AND chunked
    # appends "+mesh_chunk" (``_hierarchical_solve``).
    solved_as: str
    shard: bool  # over the mesh: the one given, else the local devices'


def solve_route(
    mode: str, rows: int, devices: int, backend: str, priced: bool,
    mesh_given: bool = False,
) -> SolveRoute:
    """The route of a full re-solve, from what the program can observe.

    ``rows`` are the directory's padded rows (all of them, not a device's
    share), ``devices`` the given mesh's or else the backend's local ones,
    ``priced`` whether per-object move prices differ. ``"auto"`` without a
    locality signal is ``sinkhorn`` on a TPU and ``greedy`` elsewhere
    (``_solver_mode`` says why). A two-level solve past
    ``_FLAT_REBALANCE_MAX_ROWS`` shards over whatever devices there are;
    under it only a mesh that was given shards anything, so a directory one
    chip's flat solve takes runs the same program on any host.
    """
    if mode == "auto":
        mode = "sinkhorn" if backend == "tpu" else "greedy"
    at_scale = rows > _FLAT_REBALANCE_MAX_ROWS
    if mode == "hierarchical":
        return SolveRoute("hierarchical", mode, mesh_given or (at_scale and devices > 1))
    if mode not in ("sinkhorn", "scaling"):
        return SolveRoute("greedy", mode, False)
    if at_scale:
        return SolveRoute("hierarchical", f"{mode}+hier_at_scale", devices > 1)
    # Per-object prices and a mesh's per-shard capacity split both break
    # the identical-cost-rows precondition of the O(M^2) class collapse.
    if priced or mesh_given:
        return SolveRoute("dense", mode, mesh_given)
    return SolveRoute("collapsed", f"{mode}+collapsed", False)


# Row cap for the affinity refine's subset solve: the communication graph
# is top-K bounded per node (EdgeSampler), so the edge-touching object set
# is small by construction; the cap is a second fence so a pathological
# merged graph can never turn the post-solve refine into a directory-sized
# dense problem. Heaviest-degree objects win the slots.
_AFFINITY_MAX_ROWS = 4096


def _next_bucket(n: int, minimum: int = 256) -> int:
    """Pad batch sizes to power-of-two buckets so XLA compiles per bucket."""
    b = minimum
    while b < n:
        b *= 2
    return b


import functools as _functools


# Key-chunk size for the streamed obj_feat builder: the feature hook is
# called on bounded slices and rows land straight in the preallocated
# final block, so host peak stays O(n_pad x d) + one chunk instead of the
# 3x the old build-pull-concat pipeline materialized at 10M+ rows.
_OBJ_FEAT_STREAM_ROWS = int(
    os.environ.get("RIO_TPU_OBJ_FEAT_STREAM_ROWS") or 262_144
)


# Rows of the directory a full solve's snapshot reads in one hold of the
# servers' loop (``_snapshot``): ~0.8 ms on the chip host at ~0.05 s a
# million rows. A constant, not an option: a directory of at most two slices
# is read in one hold, a larger one slice by slice with a turn of the loop
# between two. Sized by what a request feels, not by the slice alone: a
# request crosses the loop in ~10 hops and each hop waits for a slice and the
# turn beside it, and asyncio appends a due timer BEHIND the turn's ready
# callbacks, so a tick can run two such cycles late. At 65,536 rows (3.3 ms
# slices, ~7 ms cycles) a request due during the snapshot waited 38-78 ms in
# the median and the loop's clock logged 19-42 holds of up to 45 ms a window
# inside it; at 16,384 the median is 17 ms and the wall is the same; at 8,192
# the turns' own cost stretches the wall by half (PERF.md, Findings PR 38).
_SNAPSHOT_SLICE_ROWS = 16_384

# Rows a call of the worker thread's commit diff (``_commit_diff``) passes
# over: the comparison of the solve's output with the snapshot's seats, and
# ``np.bincount`` over the movers, which keeps the interpreter lock for its
# whole call. A constant, not an option: a solve of at most two slices is
# diffed in one call, a larger one slice by slice, and between two calls the
# interpreter can hand the lock to the loop's thread. A slice of the
# comparison reads 0.09 ms on the chip host and one of ``bincount`` 0.36 ms,
# under the ~2 ms a slice may keep the lock (PERF.md, Findings PR 41).
_DIFF_SLICE_ROWS = 262_144


def _hier_feature_dtype() -> np.dtype:
    """Host dtype for the streamed feature block.

    ``RIO_TPU_HIER_FEAT_BF16=1`` stores features as bfloat16 (``ml_dtypes``
    ships with jax) — half the host memory and half the host->device bytes
    at 10M+ rows. The solve upcasts to fp32 on device, so only feature
    PRECISION is traded (8-bit mantissa): fine for the default hashed
    identity features (quality parity pinned in tests), but keep fp32 for
    custom feature hooks that encode small learned differences.
    """
    if os.environ.get("RIO_TPU_HIER_FEAT_BF16", "0") not in ("", "0"):
        try:
            import ml_dtypes

            return np.dtype(ml_dtypes.bfloat16)
        except Exception:  # pragma: no cover - ml_dtypes rides with jax
            pass
    return np.dtype(np.float32)


@_functools.lru_cache(maxsize=8)
def _pad_feature_block(pad: int, dim: int) -> np.ndarray:
    """Deterministic features for the hierarchical solve's pad rows.

    Cached per (pad count, dim): pad row i's feature never changes, and at
    large directories rebuilding up to bucket_n-n synthetic keys + crc32
    hashes per rebalance on the solver thread would be pure waste. Callers
    only read/concatenate the returned block (never mutate)."""
    if pad == 0:
        return np.zeros((0, dim), np.float32)
    return np.asarray(
        _hash_features([f"\x00pad:{i}" for i in range(pad)], dim), np.float32
    )


def _least_loaded_spread(load, alive, cap, n_real: int, count: int) -> np.ndarray:
    """Deterministic seats when the solver can't provide them: REAL
    nodes only, schedulable (alive AND capacity > 0) nodes before the
    rest, least-loaded first — and round-robin over ONLY the
    schedulable prefix when one exists (seating overflow on a dead,
    cordoned, or capacity-zero node while schedulable capacity exists
    would break cordon's no-new-seats contract and the operator's
    capacity=0 don't-place-here signal). When NO node is schedulable
    (the all-dead blip) every real node cycles — any real seat beats a
    pad index, and an alive-but-zero-capacity node must not absorb the
    whole cluster's overflow alone. (Load alone can't order this:
    ``clean_server`` zeroes a dead node's load, ranking fresh corpses
    first.)"""
    if n_real <= 0:
        raise NoSchedulableCapacity(
            "placement solve with no registered nodes: register_node/"
            "sync_members must run before any placement is requested"
        )
    a = np.asarray(alive)[:n_real]
    c = np.asarray(cap)[:n_real]
    sched = (a > 0) & (c > 0)
    order = np.lexsort((np.asarray(load)[:n_real], ~sched))
    n_sched = int(sched.sum())
    cycle = order[:n_sched] if n_sched > 0 else order
    return cycle[np.arange(count) % len(cycle)].astype(np.int32)


def _route_unseatable(
    assignment: np.ndarray, n_real: int, load: np.ndarray, alive, cap
) -> np.ndarray:
    """Defensive clamp: solver output must index the REAL node axis.

    Solvers run over the padded power-of-two node axis; pad slots carry
    zero capacity and are normally unreachable, and the zero-schedulable-
    capacity snapshot that CAN reach them (every node dead at once) is
    short-circuited before any solve (see ``_solve_chunk`` /
    ``rebalance``). This guard is the belt-and-braces behind that: if any
    other degenerate numerical case ever clips a row onto a pad slot, a
    pad index entering the directory would blow up every later
    ``_node_order[idx]`` resolution (lookup, persistence marks, load
    recount) — route such rows through the shared spread instead.
    """
    bad = assignment >= n_real
    if not bad.any():
        return assignment  # load/alive stay un-pulled (device arrays on TPU)
    out = assignment.copy()
    out[bad] = _least_loaded_spread(
        load, alive, cap, n_real, int(bad.sum())
    ).astype(assignment.dtype)
    return out


def _cancel_transit(assignment: np.ndarray, cur_idx: np.ndarray) -> np.ndarray:
    """Re-express a plan over interchangeable rows with the fewest moves.

    An entropic plan spreads: with 1,000 schedulable nodes each survivor's
    row of the soft plan leaks a few percent of its mass off the diagonal,
    the per-row rounding turns that into a row or so sent to the emptiest
    columns (the rejoiners), and the leavers' rows fill the survivors back
    up — two moves where one would do, a thousand single-row hand-offs a
    churn event at 1,048,576 x 1,024 (PERF.md PR 27). The two-level route
    deals worse: its stay-put is a pull of 0.5 in a feature space whose
    hashed identities score every node with a standard deviation of 1, so
    a re-plan over unchanged members re-deals a quarter of the rows (94% of
    a directory the waterfill seated; PERF.md PR 34). Where every row costs
    the same to move and every destination the same to reach, a node that
    both loses and receives rows can keep what it loses: of the rows the
    plan takes off it, as many stay as it takes in, and only the rest go,
    in node order, to the nodes still short. Every node's final load is
    unchanged; no row moves that did not, and ``min(out, in)`` fewer move
    at every node. O(movers log movers), in NumPy: a two-level plan hands in
    millions of movers, and this runs beside the servers' loop.
    """
    cur = np.asarray(cur_idx, assignment.dtype)
    movers = np.flatnonzero(assignment != cur)
    if movers.size == 0:
        return assignment
    m = int(max(assignment.max(), cur.max())) + 1
    src = cur[movers]
    lose = np.bincount(src, minlength=m)
    gain = np.bincount(assignment[movers], minlength=m)
    transit = np.minimum(lose, gain)
    if not transit.any():
        return assignment
    order = np.argsort(src, kind="stable")
    src = src[order]
    rank = np.arange(movers.size) - (np.cumsum(lose) - lose)[src]
    stays = rank < transit[src]
    out = assignment.copy()
    out[movers[order[stays]]] = src[stays]
    out[movers[order[~stays]]] = np.repeat(
        np.arange(m, dtype=out.dtype), gain - transit
    )
    return out


def _commit_diff(
    assignment: np.ndarray, cur_idx: np.ndarray, seats: np.ndarray
) -> tuple[np.ndarray, np.ndarray, int]:
    """What a full solve's commit needs of its O(N) output, computed in the
    worker thread from snapshots only: the movers' positions (what
    ``np.nonzero(assignment != cur_idx)[0]`` gives), the plan's seats a node
    (what ``np.bincount(assignment, minlength=len(seats))`` gives) and the
    calls it took over the rows.

    ``seats`` are the snapshot's seats a node (``bincount(cur_idx)``, which
    the directory's per-node index has in O(nodes)): the plan's are those
    less what the movers leave plus what they take, so the one pass over
    the directory is the comparison, which lets go of the interpreter lock
    while it runs; ``np.bincount`` keeps it, and passes over movers only.
    More than two slices of ``_DIFF_SLICE_ROWS`` rows are passed slice by
    slice: no call runs for longer than a slice."""
    step = _DIFF_SLICE_ROWS
    n = max(len(assignment), 1)  # (no row: one empty call, as for one slice)
    row_step = step if n > 2 * step else n
    found = [
        np.flatnonzero(assignment[a : a + row_step] != cur_idx[a : a + row_step]) + a
        for a in range(0, n, row_step)
    ]
    movers = np.concatenate(found)
    seat_counts = seats.copy()
    for a in range(0, len(movers), step):
        rows = movers[a : a + step]
        gain = np.bincount(assignment[rows], minlength=len(seat_counts))
        gain[: len(seat_counts)] += seat_counts
        seat_counts = gain - np.bincount(cur_idx[rows], minlength=len(gain))
    return movers, seat_counts, len(found)


def _guard_sentinel_spill(repaired, real, m_axis: int, cap_alive):
    """Shared guard (see :func:`rio_tpu.ops.sinkhorn.route_sentinel_spill`);
    r4 trigger here: 10M objects, bucket 16,777,216 = exactly the fp32
    integer-precision boundary, lookup IndexError."""
    from ..ops.sinkhorn import route_sentinel_spill

    return route_sentinel_spill(repaired, real, m_axis, cap_alive)


@_functools.partial(
    jax.jit, static_argnames=("mode", "move_cost", "eps", "n_iters")
)
def _class_refresh_device(base, counts, cap_alive, g_seed, *, mode, move_cost, eps, n_iters):
    """Warm M x M class potential refresh, one jitted pipeline.

    The solvers are eager ``lax.scan`` builders — each un-jitted call
    re-traces the scan body (~160 ms of pure tracing at M=64, dwarfing
    the microseconds of device math). The jit wrapper is cached per
    (mode, shapes, config floats), so a delta event's refresh is
    sub-millisecond after the first churn event pays the compile. The
    config floats are STATIC on purpose: they change only with provider
    construction, and keeping them out of the traced arguments lets XLA
    fold the stay-put diagonal."""
    m = base.shape[0]
    ccost = jnp.broadcast_to(base[None, :], (m, m)) - (
        move_cost * jnp.eye(m, dtype=jnp.float32)
    )
    solver = scaling_sinkhorn if mode == "scaling" else sinkhorn
    _f, g, err = solver(
        ccost, counts, cap_alive, eps=eps, n_iters=n_iters, g_init=g_seed
    )
    return g, err


# -- solver convergence telemetry helpers (PR 11) ----------------------------

# Backend-compile seconds, accumulated per THREAD by a jax.monitoring
# listener registered on first use. jax reports a compile from the thread
# that asked for it, and a solve runs start to end in one worker thread, so
# a per-thread total keeps concurrent solves (N placement daemons sharing
# one provider each run theirs in ``to_thread``) out of each other's
# windows. Only the backend compile event is summed: it fires once per
# executable and includes a persistent-cache retrieval, whereas the trace
# and lowering events nest (a jit traced inside a jit is counted again by
# its parent) and the "/jax/compilation_cache/..." durations report time
# SAVED, not spent.
_COMPILE_WATCH = threading.local()
_COMPILE_LISTENER = {"registered": False}


def _compile_seconds() -> float:
    """Backend-compile seconds accumulated so far by the CALLING thread.

    Snapshot before and after a solve window, in the solve's own thread,
    to split ``solve_ms`` into compile vs execute: whatever the delta
    holds was spent inside that window, so ``compile_ms <= solve_ms``.
    """
    if not _COMPILE_LISTENER["registered"]:

        def _on_duration(event: str, duration: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILE_WATCH.total_s = (
                    getattr(_COMPILE_WATCH, "total_s", 0.0) + duration
                )

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _COMPILE_LISTENER["registered"] = True
    return getattr(_COMPILE_WATCH, "total_s", 0.0)


def _seed_warm_ratio(seed) -> float:
    """Warm fraction of a potential seed: finite entries / total.

    The solvers cold-fill non-finite seed entries to zero, so the finite
    fraction IS the warm-start hit ratio. No seed at all reads as 0.0
    (fully cold); callers pass -1 themselves for solves that take no seed.
    """
    if seed is None:
        return 0.0
    arr = np.asarray(seed)
    if arr.size == 0:
        return 0.0
    return float(np.mean(np.isfinite(arr)))


def _conv_fields(conv: dict | None) -> dict:
    """Normalize a solve's convergence record into SolveStats kwargs."""
    conv = conv or {}
    return {
        "solver_iters": int(conv.get("solver_iters", 0)),
        "residual": float(conv.get("residual", -1.0)),
        "warm_ratio": float(conv.get("warm_ratio", -1.0)),
        "compile_ms": float(conv.get("compile_ms", -1.0)),
        "exec_ms": float(conv.get("exec_ms", -1.0)),
        "chunks": int(conv.get("chunks", 0)),
        "chunk_ms": [float(x) for x in conv.get("chunk_ms", ())],
        "devices": int(conv.get("devices", 0)),
    }


def _conv_timing(conv: dict, ms: float, c0: float) -> dict:
    """Split a closed solve window of ``ms`` wall milliseconds (the
    ``solve.device`` stage's own stamps) into compile and execute."""
    conv["compile_ms"] = round((_compile_seconds() - c0) * 1e3, 3)
    conv["exec_ms"] = round(ms - conv["compile_ms"], 3)
    return conv


def _apply_class_quotas(quotas: np.ndarray, cur_idx: np.ndarray) -> np.ndarray:
    """Expand (M x M) class quotas into a per-object assignment, O(N + M^2).

    Objects within a class (= current seat) are interchangeable, so laying
    each class's own column FIRST keeps ``quotas[k, k]`` objects exactly
    where they are — the move-minimal application of the collapsed solve
    (``rio_tpu.ops.structured.class_quotas``).
    """
    m = quotas.shape[0]
    out = np.empty(cur_idx.shape[0], np.int32)
    order = np.argsort(cur_idx, kind="stable")
    counts = np.bincount(cur_idx, minlength=m)
    start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    all_cols = np.arange(m)
    for k in range(m):
        c = int(counts[k])
        if c == 0:
            continue
        cols = np.concatenate([[k], np.delete(all_cols, k)])
        targets = np.repeat(cols, quotas[k][cols])
        if targets.shape[0] < c:  # belt-and-braces vs float drift upstream
            targets = np.concatenate(
                [targets, np.full(c - targets.shape[0], k, np.int32)]
            )
        out[order[start[k] : start[k] + c]] = targets[:c]
    return out


# Anti-affinity penalty for the multi-seat (replica) solve. Relative to the
# default eps this puts cost-range/eps far beyond the exp underflow knee
# (~88) — exactly the wide-cost-range regime the PER-ROW gauge shift exists
# for (CLAUDE.md; test_scaling_survives_wide_cost_ranges). The log-domain
# sinkhorn used below is stable at any range.
_ANTI_AFFINITY_COST = 1e4


def multi_seat_plan(
    primary_idx: np.ndarray,
    k: int,
    load: np.ndarray,
    cap: np.ndarray,
    alive: np.ndarray,
    *,
    eps: float = 0.05,
    n_iters: int = 30,
) -> np.ndarray:
    """K standby seats per object under hard anti-affinity.

    The multi-seat problem collapses the same way the flat rebalance does:
    every object with the same *forbidden set* (primary + seats chosen in
    earlier rounds) has an identical cost row, so each of the K rounds is a
    class-collapsed ``(C x M)`` solve — ``C <= M`` on the first round, the
    uniform case the O(M^2) path covers — not an ``(N x M)`` one. Each
    round runs the log-domain Sinkhorn (:func:`rio_tpu.ops.sinkhorn.sinkhorn`,
    the per-row gauge-shifted semantic reference) over the class cost with
    ``_ANTI_AFFINITY_COST`` on forbidden columns, then rounds each class's
    soft plan row to integer seat quotas by largest remainder. Forbidden
    columns are zeroed before rounding, so a primary and its standbys can
    NEVER co-locate; classes with no schedulable allowed column get their
    seat back as -1 (degraded replication, never a violation).

    Returns an ``(n, k)`` int32 array of node indices, -1 for unfillable
    seats. Pure function of its snapshot inputs — safe to run in a solver
    thread (the loop-side-snapshot rule) and to property-test directly.
    """
    primary_idx = np.asarray(primary_idx, np.int64)
    n = int(primary_idx.shape[0])
    m = int(cap.shape[0])
    seats = np.full((n, k), -1, np.int32)
    if n == 0 or k <= 0:
        return seats
    load = np.asarray(load, np.float32).copy()
    cap_alive = np.asarray(cap, np.float32) * (np.asarray(alive, np.float32) > 0)
    taken = np.zeros((n, m), bool)
    has_primary = (primary_idx >= 0) & (primary_idx < m)
    taken[np.arange(n)[has_primary], primary_idx[has_primary]] = True
    for r in range(k):
        classes, inverse = np.unique(taken, axis=0, return_inverse=True)
        counts = np.bincount(inverse, minlength=classes.shape[0]).astype(
            np.float32
        )
        allowed = (~classes) & (cap_alive > 0)[None, :]
        solvable = allowed.any(axis=1)
        if not solvable.any():
            break
        # Load-aware base cost (fill ratio) + the anti-affinity wall.
        fill = load / np.maximum(cap_alive, 1e-6)
        cost = np.where(allowed, fill[None, :], _ANTI_AFFINITY_COST).astype(
            np.float32
        )
        res = sinkhorn(
            jnp.asarray(cost),
            jnp.asarray(counts * solvable),
            jnp.asarray(cap_alive),
            eps=eps,
            n_iters=n_iters,
        )
        f = np.asarray(res.f, np.float64)[:, None]
        g = np.asarray(res.g, np.float64)[None, :]
        with np.errstate(invalid="ignore"):
            expo = np.where(
                np.isfinite(f) & np.isfinite(g), f + g - cost, -np.inf
            )
        weights = np.exp(np.clip(expo / eps, -80.0, 80.0)) * allowed
        for c in np.nonzero(solvable)[0]:
            rows_c = np.nonzero(inverse == c)[0]
            w = weights[c]
            if w.sum() <= 0:
                w = allowed[c].astype(np.float64)
            share = w / w.sum() * rows_c.shape[0]
            quota = np.floor(share).astype(np.int64)
            short = rows_c.shape[0] - int(quota.sum())
            if short > 0:
                rem_order = np.argsort(-(share - quota), kind="stable")
                quota[rem_order[:short]] += 1
            targets = np.repeat(np.arange(m), quota)[: rows_c.shape[0]]
            seats[rows_c, r] = targets
            taken[rows_c, targets] = True
            np.add.at(load, targets, 1.0)
    return seats


@dataclass
class _NodeSlot:
    address: str
    capacity: float = 1.0
    alive: bool = True
    cordoned: bool = False  # drained: serving, but priced out of the solver
    load: float = 0.0
    index: int = 0
    # Measured-load capacity multiplier from sync_load (ClusterLoadView):
    # 1.0 idle, down to MIN_DERATE for an overloaded node. Quantized so
    # per-second load reports don't thrash the solve epoch.
    reported_derate: float = 1.0


@dataclass
class PlanState:
    """The previous committed solve, persisted as a first-class object.

    This is what turns the solver architecture from "re-solve the world"
    into "maintain a plan incrementally": a churn event no longer pays the
    full-directory solve — ``rebalance`` re-solves ONLY the displaced +
    new objects against the plan's residual capacity, warm-starting the
    Sinkhorn potentials from here (see ``_delta_solve``). The full solve
    remains the fallback when the displaced fraction exceeds
    ``delta_threshold``, after ``max_delta_solves`` consecutive deltas
    (staleness), or when the transport-cost audit trips (``stale``).

    Snapshot discipline: a PlanState is immutable after construction and
    atomically swapped on ``self._plan`` under the provider lock — the
    solver thread reads the snapshot it was handed, never the live field.
    """

    # (node_axis,) node potentials of the committing solve (jax array;
    # None for solves that produce none, e.g. greedy).
    g: object | None
    # (G,) coarse-stage group potentials from a hierarchical solve (numpy;
    # None for flat solves) — warm seed for the next coarse stage.
    coarse_g: object | None
    # (node_axis,) PLANNED per-node seat counts at commit. With a
    # move_sink the directory converges to this as handoffs land; delta
    # displacement is always recomputed from the live directory snapshot,
    # so this is diagnostic, not load-bearing.
    seat_counts: np.ndarray
    epoch: int  # directory epoch the plan was committed at
    liveness_fp: frozenset  # schedulable node indices at commit
    delta_solves: int = 0  # consecutive deltas since the last full solve
    stale: bool = False  # quality audit tripped: next solve goes full


@dataclass
class SolveStats:
    """Diagnostics from the last full re-solve."""

    n_objects: int = 0
    n_nodes: int = 0
    solve_ms: float = 0.0
    apply_ms: float = 0.0  # mover-only directory update (host, under lock)
    moved: int = 0
    # Objects the solve actually re-solved: the displaced set for a
    # "*+delta" solve, the whole directory for a full one.
    displaced: int = 0
    epoch: int = 0
    mode: str = "none"
    discarded: bool = False
    # -- per-solve convergence record (PR 11) --------------------------------
    # Scalars flow into `rio.placement_solve.*` gauges automatically via
    # otel.stats_gauges; -1 means "not applicable" (greedy has no
    # residual, a solve that never reached the device has no compile
    # split) — never 0, which would read as a perfect value.
    solver_iters: int = 0  # configured iterations (fixed-length scans)
    residual: float = -1.0  # final L1 column-marginal violation
    warm_ratio: float = -1.0  # finite fraction of the warm-start seed
    compile_ms: float = -1.0  # backend-compile share of solve_ms
    exec_ms: float = -1.0  # solve_ms minus compile_ms
    chunks: int = 0  # chunked-hierarchical chunk count (0 = unchunked)
    chunk_ms: list = field(default_factory=list)  # per-chunk wall ms
    # Mesh devices the solve sharded over: 1 = single-chip hierarchical,
    # 0 = not a hierarchical solve. chunks x devices is the cell count of
    # a mesh x chunk composed solve (mode suffix "+mesh_chunk").
    devices: int = 0
    # Bounded record of prior completed solves (most recent last, each with
    # an empty history of its own) — lets the daemon/operators see churn
    # cadence and whether solve/apply cost or move counts drift over time.
    history: list = field(default_factory=list)

    HISTORY_LIMIT = 32

    def history_gauges(self) -> dict[str, float]:
        """Rolling solve-history summary, scrape-ready.

        ``stats_gauges`` flattens only the last solve's scalar fields (and
        skips ``history`` — it's a list); this folds the retained window
        into trend gauges so a dashboard sees churn cadence without
        shipping the whole ring over the wire.
        """
        window = [*self.history, self] if self.mode != "none" else list(self.history)
        out = {"rio.placement_solve.history.len": float(len(window))}
        if not window:
            return out
        solves = [float(s.solve_ms) for s in window]
        out["rio.placement_solve.history.solve_ms_last"] = solves[-1]
        out["rio.placement_solve.history.solve_ms_mean"] = sum(solves) / len(solves)
        out["rio.placement_solve.history.solve_ms_max"] = max(solves)
        out["rio.placement_solve.history.moved_total"] = float(
            sum(int(s.moved) for s in window)
        )
        out["rio.placement_solve.history.delta_fraction"] = sum(
            1.0 for s in window if "delta" in str(s.mode)
        ) / len(window)
        out["rio.placement_solve.history.discarded_total"] = float(
            sum(1 for s in window if s.discarded)
        )
        # Convergence trend: last/worst residual over solves that HAVE one
        # (-1 = n/a is excluded so a greedy solve can't mask divergence),
        # plus the cumulative compile cost as a scrapeable counter.
        residuals = [float(s.residual) for s in window if s.residual >= 0.0]
        if residuals:
            out["rio.placement_solve.history.residual_last"] = residuals[-1]
            out["rio.placement_solve.history.residual_max"] = max(residuals)
        compiles = [float(s.compile_ms) for s in window if s.compile_ms >= 0.0]
        if compiles:
            out["rio.placement_solve.history.compile_ms_total"] = sum(compiles)
        # Composed-path attribution (mesh x chunk): how wide the last
        # hierarchical solves ran, and the first-chunk dispatch cost — the
        # first chunk carries any fresh compile, so a FLAT first_chunk_ms
        # across growing directories is the compile-pinning invariant made
        # scrapeable (rising = the jit cache stopped covering the shape).
        chunked = [s for s in window if int(s.chunks) > 0]
        if chunked:
            out["rio.placement_solve.history.chunks_last"] = float(
                chunked[-1].chunks
            )
            out["rio.placement_solve.history.chunks_max"] = float(
                max(int(s.chunks) for s in chunked)
            )
        meshed = [s for s in window if int(getattr(s, "devices", 0)) > 0]
        if meshed:
            out["rio.placement_solve.history.devices_last"] = float(
                meshed[-1].devices
            )
        first_chunks = [float(s.chunk_ms[0]) for s in window if s.chunk_ms]
        if first_chunks:
            out["rio.placement_solve.history.first_chunk_ms_last"] = (
                first_chunks[-1]
            )
            out["rio.placement_solve.history.first_chunk_ms_max"] = max(
                first_chunks
            )
        return out


class JaxObjectPlacement(ObjectPlacement):
    """Batched, device-solved object directory (drop-in ObjectPlacement)."""

    def __init__(
        self,
        *,
        eps: float = 0.05,
        n_iters: int = 30,
        mode: str = "auto",
        mesh=None,
        node_axis_size: int = 64,
        move_cost: float = 0.5,
        obj_features=None,
        node_features=None,
        affinity_tracker: "AffinityTracker | None" = None,
        object_costs=None,
        delta_threshold: float = 0.25,
        max_delta_solves: int = 8,
        delta_audit_ratio: float = 1.05,
        affinity_weight: float = 0.0,
        affinity_passes: int = 3,
        affinity_host_factor: float = 0.5,
        affinity_slack: float = 1.25,
    ) -> None:
        self._eps = eps
        self._n_iters = n_iters
        # Incremental (delta) rebalance knobs: a churn re-solve goes
        # through the delta path while the displaced fraction stays at or
        # below delta_threshold (0 disables deltas entirely), falls back
        # to a full solve after max_delta_solves consecutive deltas
        # (staleness bound on the warm potentials), and whenever the
        # transport-cost audit finds the delta plan worse than
        # delta_audit_ratio x the ideal quota cost.
        self._delta_threshold = delta_threshold
        self._max_delta_solves = max_delta_solves
        self._delta_audit_ratio = delta_audit_ratio
        # "auto" resolves LAZILY at the first solve: jax.default_backend()
        # initializes the jax backend, which a constructor must not do
        # (multihost.initialize has to run before any backend touch), and
        # the first actual solve initializes it anyway.
        self._mode = mode
        # A mesh that is given shards every solve it can. With none, a
        # two-level solve past _FLAT_REBALANCE_MAX_ROWS shards over the
        # backend's local devices (solve_route); that mesh is built at the
        # first such solve (_route_mesh), for the reason "auto" waits.
        self._mesh = mesh
        self._local_mesh = None
        # Stay-put discount applied to each object's CURRENT seat during a
        # full re-solve: a move costs a state reload + cold cache at the
        # application layer, so the objective must price it. With
        # move_cost/eps >> 1 the soft plan concentrates on the current seat
        # unless capacity (dead nodes, skew) forces a move — a churn
        # re-solve then moves ~the displaced share, not a global reshuffle.
        self._move_cost = move_cost
        # Hierarchical-mode feature hooks: callables (keys/addresses ->
        # (n, d) ndarray). Default is hashed identity — a deterministic
        # balancing proxy; plug an AffinityTracker (or anything encoding
        # state size / cache warmth / request rate) to make the OT affinity
        # term carry real locality signal.
        has_affinity = bool(obj_features or node_features or affinity_tracker)
        if has_affinity and mode not in ("hierarchical", "auto"):
            # Flat modes build per-node costs only and would silently
            # ignore the hooks — fail at construction, not at solve time.
            # mode="auto" is allowed: with a locality signal present it
            # resolves to "hierarchical" (see _solver_mode).
            raise ValueError(
                "obj_features/node_features/affinity_tracker are only consumed "
                f'by mode="hierarchical" (got mode={mode!r})'
            )
        self._has_affinity = has_affinity
        # Carrying the tracker on the provider lets the Server auto-wire
        # AffinityTracker.observe into the dispatch path (every served
        # request updates the object's locality feature — no app code).
        self.affinity_tracker = affinity_tracker
        if affinity_tracker is not None:
            obj_features = obj_features or affinity_tracker.obj_features
            node_features = node_features or affinity_tracker.node_features
        self._obj_features = obj_features or _hash_features
        self._node_features = node_features or _hash_features
        # Per-object move prices (keys -> (n,) weights, 1.0 = baseline):
        # scales the stay-put discount so hot/heavy actors cost more to
        # relocate than cold ones (rio_tpu/load). Works with EVERY mode
        # that prices moves (the OT solves); defaults to the tracker's
        # measured move_weights when one is wired. Uniform output is
        # equivalent to the classic scalar move_cost and keeps the
        # collapsed O(M^2) fast path; non-uniform weights route flat
        # solves through the dense (or at scale, hierarchical) pipeline.
        if object_costs is None and affinity_tracker is not None:
            object_costs = affinity_tracker.move_weights
        self._object_costs = object_costs
        # Communication-graph refinement (rio_tpu/affinity): after every
        # FULL solve, `affinity_passes` alternating linearized OT passes
        # fold the current assignment's neighbor attraction into per-object
        # cost rows and re-run the unchanged Sinkhorn core over the
        # edge-touching subset. weight 0.0 (the default) disables the term
        # entirely; the delta path never refines (its warm potentials
        # assume the pure balance objective).
        self._affinity_weight = float(affinity_weight)
        self._affinity_passes = max(1, int(affinity_passes))
        # Attraction credit for landing on a DIFFERENT worker shard of the
        # same host (same address up to the ":port"): 0 = only exact
        # co-seating counts, 1 = any same-host seat is as good as local.
        # Intermediate values make the refine optimize at two
        # granularities at once — node first, host second.
        self._affinity_host_factor = min(1.0, max(0.0, affinity_host_factor))
        # Column-capacity slack for the refine's subset solve. Strictly
        # balanced capacities provably block the simplest win (two chatty
        # objects on two equal nodes can never co-locate — either move
        # overflows a node by one), so the refine may overfill a node by
        # this factor; the acceptance check still rejects passes whose
        # total objective (balance overflow + weighted cut) regresses.
        self._affinity_slack = max(1.0, float(affinity_slack))
        # (src, dst) -> normalized byte-rate weight, undirected keys with
        # src < dst. Atomic-swap discipline: set_edge_graph builds a fresh
        # dict, the solver thread snapshots the reference.
        self._edge_graph: dict[tuple[str, str], float] = {}
        # Per-refine pass history ([{pass, cut, total, accepted}, ...]) —
        # the monotonicity evidence tests and telemetry read.
        self._affinity_history: list[dict] = []
        # Host-mirrored directory: "{type}.{id}" -> node index.
        self._placements: dict[str, int] = {}
        # Replica rows: "{type}.{id}" -> (standby addresses, epoch). Kept by
        # address (not node index) so a standby row survives node-axis
        # growth and mirrors the durable backends' schema 1:1.
        self._standby_rows: dict[str, tuple[list[str], int]] = {}
        # Per-node key index (node index -> keys): keeps clean_server and
        # load recounts O(objects-on-node), the same reason the Redis
        # backend keeps a per-server set (object_placement/redis.py).
        # The inner containers are dict[str, None], NOT sets: a set is
        # always tracked by the cycle collector, so every full collection
        # visited every key of the directory twice (~40 ms per million
        # rows on the v5e host, PERF.md PR 26). A dict that holds only str
        # and None stays untracked on CPython <= 3.13, as ``_placements``
        # does; ``place_gauges`` reports the rows an interpreter that
        # tracks every dict would walk again. Written only through
        # ``_set_placement`` / ``_drop_placement`` / ``_seat_new``.
        self._by_node: dict[int, dict[str, None]] = {}
        # What went in through the bulk seam (``place_gauges``).
        self._bulk_rows = 0
        self._bulk_chunks = 0
        # ``assign_batch`` calls answered from the seats they carried, and
        # calls that re-validated because the epoch moved (``place_gauges``).
        self._assign_carried = 0
        self._assign_revalidated = 0
        # Lattice steps ``sync_load`` applied, over all nodes (``place_gauges``).
        self._derate_steps = 0
        # Rows the delta route found displaced and moved (``place_gauges``).
        self._delta_displaced = 0
        self._delta_moved = 0
        # Committed solves that were sharded over a mesh, the devices of the
        # last and the cells (devices x chunks) of all (``place_gauges``).
        self._mesh_solves = 0
        self._mesh_devices = 0
        self._mesh_cells = 0
        # The default hook's hashed identities of the last whole-directory
        # two-level solve: ``(keys, rows)``, row i the float32 feature of
        # keys[i] before any pull. ONE atomically swapped field, neither
        # half ever mutated (two ``to_thread`` solves may overlap); see
        # ``_hashed_identities``. The counters are rows of committed solves
        # that came from it and rows that were hashed (``place_gauges``).
        self._kept_identities: tuple[list[str], np.ndarray] | None = None
        self._feat_rows_reused = 0
        self._feat_rows_hashed = 0
        # How the O(N) snapshots of the solves were read (``_snapshot``,
        # ``place_gauges``): calls read in slices, the slices of all reads,
        # reads begun again because a writer landed between two slices,
        # calls that took one hold after two such, and the slices' own time.
        self._snapshot_sliced = 0
        self._snapshot_slices = 0
        self._snapshot_restarts = 0
        self._snapshot_whole = 0
        self._snapshot_busy_ns = 0
        # The commits that took their diff from the worker thread
        # (``_commit_diff``; added in the commit's lock hold, so a discarded
        # attempt counts nowhere): commits, rows compared off the loop, the
        # calls that passed over them, the worker's ``solve.diff`` time.
        self._diff_commits = 0
        self._diff_rows = 0
        self._diff_slices = 0
        self._diff_busy_ms = 0.0
        self._nodes: dict[str, _NodeSlot] = {}
        self._node_order: list[str] = []  # index -> address (never shrinks)
        self._node_axis = node_axis_size  # static node axis (padded)
        self._epoch = 0
        self._g: jax.Array | None = None  # cached node potentials (padded axis)
        # Liveness fingerprint the cached potentials were solved over: the
        # schedulable node indices at commit. Potentials stay valid while
        # every one of those nodes REMAINS schedulable (churn on unrelated
        # nodes — registrations, dead->alive flips — never touches them);
        # a solved-over node leaving the set drops the cache (its finite g
        # entry would keep attracting the warm assign_batch path).
        self._g_fp: frozenset | None = None
        # Previous committed solve (potentials + seat counts + epoch) —
        # the incremental-rebalance state. See PlanState.
        self._plan: PlanState | None = None
        # Liveness-flip subscribers (the placement daemon's event kick).
        self._churn_listeners: list = []
        self._lock = asyncio.Lock()
        self._cache_placed = False  # see _place_compile_cache
        self.stats = SolveStats()

    def _place_compile_cache(self) -> None:
        """Place the persistent compile cache once, at this provider's
        first solve (it touches the backend, so never the constructor):
        a user's server gets it the way the smoke does."""
        if not self._cache_placed:
            compile_cache_dir()
            self._cache_placed = True

    def _solver_mode(self) -> str:
        """Resolve ``mode="auto"`` on first use (first backend touch).

        The rule (see ``tests/test_affinity_payoff.py``; the on-chip
        figures below are the r5 capture, not re-measured):

        * **locality signal present** (an ``AffinityTracker`` or feature
          hooks were wired) → ``hierarchical``: it is the only mode that
          consumes per-object affinity, its payoff is large (~4x fewer
          state reloads after churn on a warm-traffic workload), and its
          O(N*(G+S+d)) cost is accelerator-independent — cheaper than the
          dense solve everywhere.
        * otherwise, per-node costs only: the dense OT solve wins on an
          accelerator (measured 35x the SQL baseline on TPU v5e) but loses
          to the thing it replaces on host CPUs, where the O(N log M)
          greedy waterfill is the right default (measured ~26x the
          baseline). Flat OT rebalances additionally collapse to O(M^2)
          either way (see ``rebalance``).
        """
        if self._mode == "auto":
            if self._has_affinity:
                self._mode = "hierarchical"
            else:
                self._mode = (
                    "sinkhorn" if jax.default_backend() == "tpu" else "greedy"
                )
        return self._mode

    def _route_devices(self) -> int:
        """Devices a solve may shard over: the given mesh's, else the
        backend's local ones (a backend touch, like ``_solver_mode``)."""
        if self._mesh is not None:
            return int(self._mesh.devices.size)
        return jax.local_device_count()

    def _route_mesh(self, route: SolveRoute):
        """The mesh ``route`` shards over, or None: the one given, else one
        over the local devices, built the first time a route asks for it."""
        if not route.shard:
            return None
        if self._mesh is not None:
            return self._mesh
        if self._local_mesh is None:
            from ..parallel import make_mesh

            self._local_mesh = make_mesh(jax.local_devices())
        return self._local_mesh

    def _archived_history(self) -> list:
        """Current stats (if any solve/attempt happened) appended to its
        own history, flattened and bounded — the record the NEXT stats
        object carries. Lock held by callers."""
        prior = self.stats
        if not prior.epoch:  # the never-solved default carries no event
            return []
        return (prior.history + [replace(prior, history=[])])[
            -SolveStats.HISTORY_LIMIT:
        ]

    # -------------------------------------------- potentials / churn events
    def _sched_fp(self) -> frozenset:
        """Schedulable-node fingerprint: indices of nodes that can take
        NEW seats right now (alive, not cordoned, capacity > 0)."""
        return frozenset(
            s.index
            for s in self._nodes.values()
            if s.alive and not s.cordoned and s.capacity > 0
        )

    def _invalidate_potentials(self) -> None:
        """Version the cached potentials by liveness fingerprint instead
        of nulling them on every membership event: ``_g`` survives churn
        on UNRELATED nodes (new registrations, dead->alive recoveries,
        uncordons — their g entries are -inf, so the warm ``assign_batch``
        path never seats there until the next solve refreshes them, which
        is merely conservative). Only a solved-over node LEAVING the
        schedulable set (death, cordon, capacity loss) drops the cache:
        its finite potential would keep pulling new placements onto a node
        that must not take them."""
        if self._g is None:
            return
        if self._g_fp is None or not (self._g_fp <= self._sched_fp()):
            self._g = None
            self._g_fp = None

    def add_churn_listener(self, cb) -> None:
        """Register a zero-arg callable fired after every liveness-affecting
        change (``sync_members`` flips, ``cordon``/``uncordon``,
        ``clean_server``). Fired on the event loop, synchronously with the
        mutation — listeners must only flag/schedule (the placement
        daemon's event kick sets an ``asyncio.Event``), never block."""
        self._churn_listeners.append(cb)

    def _notify_churn(self) -> None:
        for cb in list(self._churn_listeners):
            try:
                cb()
            except Exception:  # noqa: BLE001 - listeners never break liveness
                pass

    # ------------------------------------------------- directory internals
    def _set_placement(self, key: str, idx: int) -> bool:
        """Point ``key`` at node ``idx`` keeping the per-node index in sync.

        Returns True when the placement actually changed (lock held).
        """
        old = self._placements.get(key)
        if old == idx:
            return False
        if old is not None:
            self._by_node.get(old, {}).pop(key, None)
        self._placements[key] = idx
        self._by_node.setdefault(idx, {})[key] = None
        return True

    def _drop_placement(self, key: str) -> int | None:
        idx = self._placements.pop(key, None)
        if idx is not None:
            self._by_node.get(idx, {}).pop(key, None)
        return idx

    def _seat_new(self, keys: list[str], idx: np.ndarray) -> np.ndarray:
        """Seat ``keys`` at node indices ``idx`` in bulk (lock held): the
        twin of ``_set_placement`` for keys that have NO seat, each
        occurring once (``assign_batch`` establishes both under the lock).
        One write per container instead of one call per key; a key that
        moves goes through ``_set_placement``. Returns the seats taken per
        node index.
        """
        self._placements.update(zip(keys, idx.tolist()))
        counts = np.bincount(idx, minlength=len(self._node_order))
        # Stable: a node's keys enter its index in the chunk's order, as
        # the per-key seam would have entered them.
        grouped = np.array(keys, dtype=object)[np.argsort(idx, kind="stable")]
        start = 0
        for j in np.flatnonzero(counts).tolist():
            end = start + int(counts[j])
            self._by_node.setdefault(j, {}).update(
                dict.fromkeys(grouped[start:end].tolist())
            )
            start = end
        self._bulk_rows += len(keys)
        self._bulk_chunks += 1
        return counts

    def place_gauges(self) -> dict[str, float]:
        """``rio.place.*`` for ``otel.server_gauges``, made at scrape time
        in O(nodes). ``index_tracked_rows`` reads 0 while no per-key entry
        of the mirror sits in a container the collector walks."""
        return {
            "rio.place.bulk_rows": float(self._bulk_rows),
            "rio.place.bulk_chunks": float(self._bulk_chunks),
            "rio.place.assign_carried": float(self._assign_carried),
            "rio.place.assign_revalidated": float(self._assign_revalidated),
            "rio.load.derate_steps": float(self._derate_steps),
            "rio.place.delta.displaced": float(self._delta_displaced),
            "rio.place.delta.moved": float(self._delta_moved),
            "rio.solve.mesh.solves": float(self._mesh_solves),
            "rio.solve.mesh.devices": float(self._mesh_devices),
            "rio.solve.mesh.cells": float(self._mesh_cells),
            "rio.solve.features.rows_reused": float(self._feat_rows_reused),
            "rio.solve.features.rows_hashed": float(self._feat_rows_hashed),
            "rio.place.snapshot.sliced": float(self._snapshot_sliced),
            "rio.place.snapshot.slices": float(self._snapshot_slices),
            "rio.place.snapshot.restarts": float(self._snapshot_restarts),
            "rio.place.snapshot.whole": float(self._snapshot_whole),
            "rio.place.snapshot.busy_ms": self._snapshot_busy_ns / 1e6,
            "rio.solve.diff.commits": float(self._diff_commits),
            "rio.solve.diff.rows": float(self._diff_rows),
            "rio.solve.diff.slices": float(self._diff_slices),
            "rio.solve.diff.busy_ms": self._diff_busy_ms,
            "rio.place.index_tracked_rows": float(
                sum(len(c) for c in self._by_node.values() if gc.is_tracked(c))
            ),
        }

    def _set_standby_row(self, key: str, addresses: list[str], epoch: int) -> None:
        """Single mutation seam for replica rows (lock held) — like
        ``_set_placement``, so write-behind subclasses see every change."""
        self._standby_rows[key] = (list(addresses), epoch)

    def _drop_standby_row(self, key: str) -> None:
        self._standby_rows.pop(key, None)

    # ---------------------------------------------------------------- nodes
    def _node_index(self, address: str) -> int:
        slot = self._nodes.get(address)
        if slot is None:
            idx = len(self._node_order)
            if idx >= self._node_axis:
                # Grow the static node axis (rare; forces one recompile
                # tier). Cached potentials AND the incremental plan carry
                # old-axis shapes — both must go.
                self._node_axis *= 2
                self._g = None
                self._g_fp = None
                self._plan = None
            slot = _NodeSlot(address=address, index=idx)
            self._nodes[address] = slot
            self._node_order.append(address)
            self._epoch += 1
        return slot.index

    def register_node(self, address: str, *, capacity: float = 1.0) -> None:
        idx = self._node_index(address)
        self._nodes[address].capacity = capacity
        self._nodes[address].alive = True

    def sync_members(self, members) -> None:
        """Feed gossip liveness into the cost model.

        ``members`` is an iterable with ``address()``/``active`` (the shape of
        ``rio_tpu.cluster.storage.Member``). Unknown members are registered;
        known members get their liveness updated. Dead nodes keep their index
        (static shapes) but are priced out of the cost matrix.
        """
        seen = set()
        changed = False
        for m in members:
            addr = getattr(m, "address", None)
            if callable(addr):
                addr = addr()
            if addr is None:
                addr = str(m)
            active = bool(getattr(m, "active", True))
            seen.add(addr)
            if addr not in self._nodes:
                self._node_index(addr)
                changed = True
            slot = self._nodes[addr]
            if slot.alive != active:
                slot.alive = active
                changed = True
        for addr, slot in self._nodes.items():
            if addr not in seen and slot.alive:
                slot.alive = False
                changed = True
        if changed:
            self._epoch += 1
            # Fingerprint-versioned, NOT nulled: churn on unrelated nodes
            # (new members, dead->alive recoveries) keeps the warm cache;
            # only a solved-over node leaving the schedulable set drops it.
            self._invalidate_potentials()
            self._notify_churn()

    # Derates quantize to 1/8 steps: sync_load runs every monitor tick
    # (~seconds), and an un-quantized float would re-price every node on
    # every call, so each re-solve would shuffle seats for measurement
    # noise. A bucket flip is a real regime change.
    _DERATE_STEP = 8.0
    # ...with hysteresis: a node leaves the step it is on only for a price at
    # least this share of a step away from it. A price that sits on the edge
    # between two steps (a server whose tick runs ~7 ms late behind its
    # co-located siblings' ticks reads 0.9375, PERF.md PR 27) would otherwise
    # flap between them on every call, and one flap of one server moves every
    # node's quota by a row: a thousand single-row hand-offs a flap.
    _DERATE_HOLD = 0.75

    def sync_load(self, view) -> None:
        """Feed measured cluster load (``rio_tpu.load.ClusterLoadView``)
        into the cost model: each node's solver capacity column becomes
        ``capacity * derate``. Loop-side and lock-free, exactly like
        ``sync_members``; called by the LoadMonitor's view refresh and
        the placement daemon's poll. ``view=None`` (or an unknown/stale
        entry) resets a node to its full capacity.

        A derate is a PRICE, not a directory fact, so it does not move
        the epoch: a solve in flight commits against the capacities it
        snapshotted, which is what every committed solve is one tick
        later anyway. (It used to bump the epoch. A server that shares
        the directory's process reads a long solve's host work as its own
        loop lag, so the solve derated its neighbours, the flip discarded
        the solve — on the v5e every cold-compile solve of the
        1,048,576-row directory, 150 s of work, PR 21 — and under load
        that wobbles a churn re-solve could lose every retry.) The epoch
        guards what an apply can corrupt: seats and liveness."""
        changed = False
        # A node can only step if the view prices it or it stands off full
        # price now: at a thousand members of which a few report load, the
        # rest is one comparison each and not a pricing.
        priced = getattr(view, "entries", None)
        for addr, slot in self._nodes.items():
            if priced is not None and slot.reported_derate == 1.0 and addr not in priced:
                continue
            d = 1.0 if view is None else float(view.derate(addr))
            if not (d == d):  # NaN guard (view sanitizes; belt-and-braces)
                d = 1.0
            d = min(1.0, max(0.1, d))
            if abs(d - slot.reported_derate) * self._DERATE_STEP < self._DERATE_HOLD:
                continue
            q = round(d * self._DERATE_STEP) / self._DERATE_STEP
            if q != slot.reported_derate:
                self._derate_steps += round(
                    abs(q - slot.reported_derate) * self._DERATE_STEP
                )
                slot.reported_derate = q
                changed = True
        if changed:
            # Derates floor at 0.1 and never zero a capacity column, so no
            # node LEAVES the schedulable set here — the fingerprint check
            # keeps the potentials (they merely under-react to the new
            # derate until the next solve refreshes them). No churn
            # notification: load drift is the daemon's normal poll work.
            self._invalidate_potentials()

    # --------------------------------------------------------------- drain
    def cordon(self, address: str) -> None:
        """Drain a node gracefully (the kubectl-cordon analog; no reference
        counterpart — its only exit is death + lazy re-allocation).

        The node keeps serving its current objects, but the solver prices
        it like a dead node: no NEW allocations land there, and the next
        ``rebalance()`` re-seats its population onto the remaining nodes —
        moving exactly that share, per the stay-put discount. Then stop the
        server with nothing displaced. Loop-side and lock-free, like
        ``sync_members`` (the snapshot-solve-apply discipline covers it).
        """
        slot = self._nodes.get(address)
        if slot is None:
            raise KeyError(f"unknown node {address!r}")
        if slot.cordoned:
            return
        others = any(
            s.alive and not s.cordoned and s.capacity > 0
            for a, s in self._nodes.items()
            if a != address
        )
        if not others:
            raise RuntimeError(
                f"refusing to cordon {address!r}: no other schedulable "
                f"node would remain"
            )
        slot.cordoned = True
        self._epoch += 1
        self._invalidate_potentials()
        self._notify_churn()

    def uncordon(self, address: str) -> None:
        slot = self._nodes.get(address)
        if slot is None:
            raise KeyError(f"unknown node {address!r}")
        if slot.cordoned:
            slot.cordoned = False
            self._epoch += 1
            self._invalidate_potentials()
            self._notify_churn()

    @property
    def cordoned(self) -> set[str]:
        return {a for a, s in self._nodes.items() if s.cordoned}

    # ------------------------------------------------------- device vectors
    def _node_vectors(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        n = self._node_axis
        load = np.zeros((n,), np.float32)
        cap = np.zeros((n,), np.float32)
        alive = np.zeros((n,), np.float32)
        for addr in self._node_order:
            s = self._nodes[addr]
            load[s.index] = s.load
            # Measured load shrinks the capacity column (sync_load): a hot
            # node takes proportionally fewer new/rebalanced seats, with a
            # floor so it never vanishes from the solve entirely.
            cap[s.index] = s.capacity * s.reported_derate
            # Cordoned nodes price exactly like dead ones (no NEW seats; a
            # rebalance drains them) — but their directory rows stand and
            # they keep serving until the operator stops them.
            alive[s.index] = 1.0 if (s.alive and not s.cordoned) else 0.0
        return jnp.asarray(load), jnp.asarray(cap), jnp.asarray(alive)

    def _no_schedulable_capacity_host(self) -> bool:
        """Loop-side zero-capacity predicate over HOST node state, taken at
        the same moment as the ``_node_vectors`` snapshot. Never reads the
        device arrays: a device->host pull is a sync point, and this
        predicate runs on every chunk and every rebalance."""
        return not any(
            s.alive and not s.cordoned and s.capacity > 0
            for s in self._nodes.values()
        )

    def _recount_loads(self) -> None:
        for s in self._nodes.values():
            s.load = float(len(self._by_node.get(s.index, ())))

    # ------------------------------------------------------ trait: lookups
    async def update(self, item: ObjectPlacementItem) -> None:
        key = str(item.object_id)
        async with self._lock:
            if item.server_address is None:
                self._drop_placement(key)
            else:
                self._set_placement(key, self._node_index(item.server_address))
            self._epoch += 1

    async def lookup(self, object_id: ObjectId) -> str | None:
        idx = self._placements.get(str(object_id))
        if idx is None:
            return None
        addr = self._node_order[idx]
        return addr

    async def clean_server(self, address: str) -> None:
        async with self._lock:
            slot = self._nodes.get(address)
            if slot is None:
                return
            slot.alive = False
            slot.load = 0.0  # its placements are gone; keep fair-share math honest
            # O(objects-on-node) via the per-node index — a full-directory
            # scan here would be a multi-second GIL stall at the 10M tier.
            # Dropped through _drop_placement (the single mirror-mutation
            # seam) so subclasses tracking writes see every key.
            for k in list(self._by_node.get(slot.index, ())):
                self._drop_placement(k)
            self._by_node.pop(slot.index, None)
            self._epoch += 1
            self._invalidate_potentials()
            self._notify_churn()

    async def remove(self, object_id: ObjectId) -> None:
        async with self._lock:
            key = str(object_id)
            if key in self._standby_rows:
                self._drop_standby_row(key)
            if self._drop_placement(key) is not None:
                self._epoch += 1

    def count(self) -> int:
        return len(self._placements)

    # ------------------------------------------------------- replica rows
    async def set_standbys(self, object_id: ObjectId, addresses: list[str]) -> int:
        key = str(object_id)
        async with self._lock:
            _, epoch = self._standby_rows.get(key, ([], 0))
            if addresses or epoch:
                self._set_standby_row(key, list(addresses), epoch)
            elif key in self._standby_rows:
                self._drop_standby_row(key)
            return epoch

    async def standbys(self, object_id: ObjectId) -> tuple[list[str], int]:
        # Lock-free read, like lookup(): single-assignment snapshot of an
        # immutable (list, epoch) tuple.
        held, epoch = self._standby_rows.get(str(object_id), ([], 0))
        return sanitize_standby_row(held, epoch)

    async def promote_standby(
        self, object_id: ObjectId, address: str, expected_epoch: int
    ) -> int | None:
        key = str(object_id)
        async with self._lock:
            held, epoch = self._standby_rows.get(key, ([], 0))
            if epoch != expected_epoch or address not in held:
                return None
            self._set_standby_row(
                key, [a for a in held if a != address], epoch + 1
            )
            self._set_placement(key, self._node_index(address))
            self._epoch += 1
            return epoch + 1

    async def assign_standbys(
        self, object_ids: list[ObjectId], k: int = 1
    ) -> list[list[str]]:
        """Compute K anti-affinity standby seats per object (compute only —
        the caller persists the choice through :meth:`set_standbys`, so the
        epoch fence stays in one place).

        Snapshot-solve discipline as everywhere else: node vectors and
        primary seats are snapshotted under the lock on the loop, the
        class-collapsed :func:`multi_seat_plan` runs in a thread, and no
        live provider state is read from that thread.
        """
        if not object_ids or k <= 0:
            return [[] for _ in object_ids]
        self._place_compile_cache()
        async with self._lock:
            keys = [str(o) for o in object_ids]
            primary = np.asarray(
                [self._placements.get(key, -1) for key in keys], np.int64
            )
            load, cap, alive = self._node_vectors()
            node_order = list(self._node_order)
            no_capacity = self._no_schedulable_capacity_host()
        if no_capacity:
            return [[] for _ in object_ids]
        eps, n_iters = self._eps, self._n_iters

        def _solve() -> np.ndarray:
            return multi_seat_plan(
                primary,
                k,
                np.asarray(load),
                np.asarray(cap),
                np.asarray(alive),
                eps=eps,
                n_iters=n_iters,
            )

        seats = await asyncio.to_thread(_solve)
        n_real = len(node_order)
        return [
            [node_order[j] for j in row if 0 <= j < n_real]
            for row in seats
        ]

    # ------------------------------------------------------- batched solve
    async def lookup_batch(self, object_ids: list[ObjectId]) -> list[str | None]:
        with stage("place.lookup"):
            # The key is ``str(ObjectId)`` spelled inline: no call a key.
            get, order = self._placements.get, self._node_order
            return [
                None if (idx := get(f"{o.type_name}.{o.id}")) is None else order[idx]
                for o in object_ids
            ]

    @contextlib.asynccontextmanager
    async def _lock_staged(self):
        """``async with self._lock``, the wait logged as ``place.lock_wait``."""
        with stage("place.lock_wait", wait=True):
            await self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    async def assign_batch(self, object_ids: list[ObjectId]) -> list[str]:
        """Place a batch of (possibly new) objects in one device call.

        Already-placed objects keep their seat; unplaced ones are assigned via
        the cached node potentials when available (incremental fast path),
        falling back to a greedy balanced solve. This is the replacement for
        the reference's one-SQL-roundtrip-per-object allocate
        (``service.rs:241-253``).

        The lock is taken PER CHUNK, not across the whole batch:
        a 10M-key batch solves for ~46 s, and holding ``self._lock`` across
        it starved ``update``/``remove``/``clean_server``/``rebalance`` and
        every other ``assign_batch`` caller for the duration. Each chunk
        probes membership under its lock hold (two callers racing on
        overlapping keys place each key once) and keeps what the probe and
        the solve told it: the seat index of every key of the chunk.

        The answer is built from those carried seats, inside the last
        chunk's lock hold, as long as ``self._epoch`` at every acquisition
        is what it was when the previous hold ended: every writer of seats
        bumps it under the lock, so nobody else wrote and every carried seat
        still stands (a batch of one chunk has no gap to check). Only when
        the epoch did move between two holds (an ``update``/``remove``/
        ``clean_server``/committed solve, or a liveness change) does the
        final resolution re-validate: keys dropped meanwhile are re-placed
        under one last lock hold, with no unlocked await between that
        re-place and the per-key read, so the resolution cannot miss.

        Raises :class:`rio_tpu.errors.NoSchedulableCapacity` (a
        ``ValueError`` subclass) when no node has registered yet — the
        batch cannot be seated anywhere, and silently parking it would
        strand every key.
        """
        if not object_ids:
            return []
        # The stages below tile the call (PERF.md section 3 names the metric
        # that reads each): one record per stage per chunk, nothing per key.
        with stage("place.assign"):
            with stage("place.keys"):
                # ``str(ObjectId)`` spelled inline: no call a key.
                keys = [f"{o.type_name}.{o.id}" for o in object_ids]
                # A key given twice reaches the solve once: solved twice it
                # was moved by its second row while its first node kept a
                # load of +1 it did not hold.
                uniq = keys if len(set(keys)) == len(keys) else list(dict.fromkeys(keys))
            carried: list[np.ndarray] = []  # seat index per key of uniq, by chunk
            left_at = None  # the epoch when the previous hold ended
            clean = True
            for start in range(0, len(uniq), self._MAX_PLACE_CHUNK):
                async with self._lock_staged():
                    if left_at is not None and self._epoch != left_at:
                        clean = False
                    with stage("place.filter"):
                        chunk = uniq[start : start + self._MAX_PLACE_CHUNK]
                        # The common wave, every key new, is one pass in C
                        # that builds nothing; it ends at the first seated key.
                        if self._placements.keys().isdisjoint(chunk):
                            held, unplaced = None, chunk
                        else:
                            held = list(map(self._placements.get, chunk))
                            unplaced = [k for k, i in zip(chunk, held) if i is None]
                    seats = await self._place_chunk_locked(unplaced) if unplaced else ()
                    if clean:
                        if held is not None:
                            found = np.fromiter(
                                (-1 if i is None else i for i in held), np.int64, len(held)
                            )
                            found[found < 0] = seats
                            seats = found
                        carried.append(seats)
                    left_at = self._epoch
                    if clean and start + self._MAX_PLACE_CHUNK >= len(uniq):
                        self._assign_carried += 1
                        with stage("place.resolve"):
                            idx = np.concatenate(carried)
                            if uniq is not keys:
                                at = dict(zip(uniq, range(len(uniq))))
                                idx = idx[
                                    np.fromiter(map(at.__getitem__, keys), np.int64, len(keys))
                                ]
                            return np.array(self._node_order, object)[idx].tolist()
            # Somebody else wrote between two of the holds above.
            self._assign_revalidated += 1
            async with self._lock_staged():
                with stage("place.filter"):
                    missing = [k for k in uniq if k not in self._placements]
                if missing:
                    await self._place_keys_async(missing)
                with stage("place.resolve"):
                    return [self._node_order[self._placements[k]] for k in keys]

    # Bounds the (bucket x node_axis) working set of one placement solve:
    # 262,144 x 1,024 fp32 is ~1 GB of sort/cumsum temps. A single
    # unchunked 10M-key batch padded its bucket to 16.7M rows and
    # materialized ~100 GB of temps on the CPU backend (r4) — chunking
    # keeps any batch size at a constant footprint, and the waterfill
    # carries the updated node load into the next chunk so balance holds
    # across the whole batch.
    _MAX_PLACE_CHUNK = 262_144

    async def _place_keys_async(self, keys: list[str]) -> None:
        """Chunked placement under a CALLER-held lock (straggler path)."""
        for start in range(0, len(keys), self._MAX_PLACE_CHUNK):
            await self._place_chunk_locked(keys[start : start + self._MAX_PLACE_CHUNK])

    async def _place_chunk_locked(self, chunk: list[str]) -> np.ndarray:
        """One chunk's placement with the device solve OFF the event loop;
        returns the node index each key of ``chunk`` was seated at.

        Snapshot-solve-apply, the same discipline as ``rebalance``: the
        node vectors and cached potentials are snapshotted ON the event
        loop (so lock-free mutators like ``sync_members``/``register_node``,
        which run on the loop, can never tear them mid-read), the solve
        runs in a thread against only those snapshots, and the cheap host
        apply runs back on the loop. The caller holds ``self._lock`` across
        the awaits, so no other locked mutator interleaves within a chunk;
        lock-free dict reads (``lookup``) stay live throughout.
        """
        with stage("place.snapshot"):
            self._place_compile_cache()
            # Snapshot here, not at batch start: the previous chunk's apply
            # (and, between lock holds, any interleaved mutator) changed load.
            load, cap, alive = self._node_vectors()
            g = self._g
            n_real = len(self._node_order)  # snapshot: the thread reads no live state
            no_capacity = self._no_schedulable_capacity_host()

        def _solve() -> tuple[np.ndarray, int]:
            with stage("place.solve") as st:
                out = self._solve_chunk(chunk, load, cap, alive, g, n_real, no_capacity)
            return out, st.t1

        assignment, t_solved = await asyncio.to_thread(_solve)
        # From the thread's last instant to this coroutine running again:
        # the wait for a busy loop.
        stage_since("place.resume", t_solved)
        with stage("place.apply"):
            self._apply_chunk(chunk, assignment)
        return assignment

    def _solve_chunk(
        self, keys, load, cap, alive, g, n_real, no_capacity=False
    ) -> np.ndarray:
        """Device solve for one placement chunk over loop-side snapshots;
        reads NO live provider state, mutates nothing (thread-safe)."""
        n = len(keys)
        if no_capacity:
            # Every node dead (or cordoned) at once, e.g. a clean_server
            # storm or a gossip blip marking the whole cluster inactive
            # between ticks (found by the 80-wave soak at wave 46). The
            # waterfill degenerates here (all-zero widths clip every row
            # onto one worst-scored slot, real or pad), so don't solve:
            # seat deterministically via the shared spread. Reference
            # semantics: placement rows outlive their owner
            # (rio-rs/src/service.rs:213-238 re-seats on the next
            # request); the next liveness change re-solves.
            return _least_loaded_spread(load, alive, cap, n_real, n)
        with stage("place.solve.build"):  # up to the jitted call's return
            cost = build_cost_matrix(load, cap, alive)  # (1, n_nodes)
            if g is not None:
                # Warm path: bias the score by the cached node potentials from
                # the last OT solve, then waterfill (balance even under cost
                # ties).
                g = jnp.where(jnp.isfinite(g), g, -1e9)
                cost = cost - g[None, :]
            bucket = _next_bucket(n)
            rows = jnp.broadcast_to(cost, (bucket, cost.shape[1]))
            mass = jnp.concatenate(
                [jnp.ones((n,), jnp.float32), jnp.zeros((bucket - n,), jnp.float32)]
            )
            seats = greedy_balanced_assign(rows, mass, cap * alive, load)
        with stage("place.solve.wait", wait=True):  # the device, and the transfer back
            seats = np.asarray(seats)[:n]
        with stage("place.solve.route"):
            return _route_unseatable(seats, n_real, load, alive, cap)

    def _apply_chunk(self, keys: list[str], assignment: np.ndarray) -> None:
        counts = self._seat_new(keys, assignment)
        for j in np.flatnonzero(counts).tolist():
            self._nodes[self._node_order[j]].load += float(counts[j])
        self._epoch += 1

    def _hook_rows(self, keys: list[str]) -> np.ndarray:
        """The feature hook's float32 rows for one bounded slice of keys.

        Sanitize is load-bearing, not belt-and-braces: measured load
        vectors reach the solver only through ``ClusterLoadView``'s
        sanitization, but feature hooks are user code with no such gate —
        one NaN row would propagate through the coarse cost std and
        poison EVERY object's normalized cost. Non-finite entries become
        0.0 (a zero feature row still spreads correctly under the
        capacity marginals; copy-on-write, since the hook may hand us its
        internal buffer).
        """
        feats = np.asarray(self._obj_features(keys), np.float32)
        if not np.isfinite(feats).all():
            feats = np.nan_to_num(feats, nan=0.0, posinf=0.0, neginf=0.0)
        return feats

    def _hashed_identities(self, keys: list[str]) -> tuple[np.ndarray, int]:
        """The default hook's rows for a whole-directory snapshot, from the
        block the last such solve kept where the keys are where they were.

        A hashed identity is a pure function of its key string, so the
        rows are kept positionally beside the key list they were made
        from. Validity is checked here, in the solver thread, and promised
        by nobody: the kept list must equal the first ``len(kept)`` keys of
        this snapshot (one list comparison in C that compares pointers
        first, and a snapshot hands out the directory's own key objects:
        tens of ms at 4M rows, nothing per key in Python). Then only the
        tail is hashed — a dict keeps insertion order and ``update()``
        keeps a key's place, so a directory that grows appends. Anything
        else (a key removed, ``clean_server`` un-seating rows, another
        order) hashes every row as before. Returns ``(rows, reused)``;
        ``rows`` is read-only and shared with later solves. Costs 64 B a
        row of host memory while the directory solves this way.
        """
        n = len(keys)  # never 0: an empty directory makes no solve
        kept_keys, old = self._kept_identities or ([], None)
        m = len(kept_keys)
        reused = m if 0 < m <= n and kept_keys == (keys if m == n else keys[:m]) else 0
        if reused == n:
            return old, n
        rows: np.ndarray | None = None
        step = max(1, _OBJ_FEAT_STREAM_ROWS)
        for start in range(reused, n, step):
            feats = self._hook_rows(keys[start : start + step])
            if rows is None:
                rows = np.empty((n, feats.shape[1]), np.float32)
                if reused:
                    rows[:reused] = old
            rows[start : start + len(feats)] = feats
        rows.flags.writeable = False
        self._kept_identities = (keys, rows)  # atomic swap
        return rows, reused

    def _build_obj_feat(
        self, keys: list[str], n_pad: int, node_order: list[str],
        cur_idx, move_cost: float, move_w, whole: bool = False,
    ) -> tuple[np.ndarray, int, int]:
        """Streamed (n_pad, d) object-feature block for a hierarchical solve.

        The old pipeline materialized three full-size intermediates at once
        (raw features, the stay-put pull, and the padded concat) — 1.9 GB
        of throwaway peak at 10M x 16 fp32. This builder preallocates the
        FINAL block once and fills it in bounded key-chunks
        (``_OBJ_FEAT_STREAM_ROWS``): per chunk it takes the feature rows,
        applies the stay-put pull, and writes rows in place —
        peak is the output plus one chunk. ``RIO_TPU_HIER_FEAT_BF16=1``
        stores the block in bfloat16 (:func:`_hier_feature_dtype`).

        Where the rows come from. ``whole`` says the keys are the
        directory's whole snapshot in its order (a full re-solve, not a
        delta's displaced subset). With the default hook such a solve takes
        its rows from :meth:`_hashed_identities`, which hashes only keys it
        has not seen at their place; the block is the same to the last bit
        either way, since the pull, the pad rows and the dtype are applied
        here, per solve. A user hook depends on traffic: it is called on
        every key at every solve (:meth:`_hook_rows`) and nothing is kept.
        Returns ``(block, rows_reused, rows_hashed)``; both counts are 0
        under a user hook and for a subset.

        Pad rows (``n_pad - n``: po2 bucket padding plus the mesh's
        shard-multiple round-up) come from the cached deterministic block
        — they ride the solve as ordinary rows and are sliced off by the
        caller's ``[:n]``.
        """
        n = len(keys)
        dtype = _hier_feature_dtype()
        identities = None
        reused = 0
        if whole:
            if self._obj_features is _hash_features and n:
                identities, reused = self._hashed_identities(keys)
            else:
                self._kept_identities = None
        node_emb = None
        seat = None
        if move_cost > 0.0 and cur_idx is not None and node_order:
            # Stay-put pull for routed flat-mode solves (see
            # _hierarchical_solve docstring): adding move_cost of the
            # current seat's unit embedding raises the seat's affinity by
            # move_cost. A BIAS, not a fence: a hashed identity scores every
            # node with a standard deviation of 1 (16 standard normals
            # against a unit vector), so at move_cost 0.5 most rows still
            # follow their hash. What keeps a routed re-plan's rows in
            # place is _cancel_transit on the plan's loads (rebalance).
            node_emb = np.asarray(self._node_features(node_order), np.float32)
            seat = np.asarray(cur_idx, np.int64)
        out: np.ndarray | None = None
        step = max(1, _OBJ_FEAT_STREAM_ROWS)
        for start in range(0, n, step):
            end = min(n, start + step)
            if identities is not None:
                feats = identities[start:end]
            else:
                feats = self._hook_rows(keys[start:end])
            if out is None:
                out = np.empty((n_pad, feats.shape[1]), dtype)
            if node_emb is not None:
                s = seat[start:end]
                seated = (s >= 0) & (s < len(node_order))
                if seated.all():
                    pull = node_emb.take(s, axis=0)
                else:
                    pull = np.zeros_like(feats)
                    pull[seated] = node_emb[s[seated]]
                if move_w is not None:
                    # Per-object move prices (object_costs): a hot/heavy
                    # actor's pull toward its current seat scales with its
                    # measured weight, mirroring the dense path's scaled
                    # stay-put discount.
                    pull *= np.asarray(move_w[start:end], np.float32)[:, None]
                pull *= np.float32(move_cost)
                feats = np.add(feats, pull, out=pull)
            out[start:end] = feats
        if out is None:  # empty directory: shape from the hook's contract
            probe = np.asarray(self._obj_features([]), np.float32)
            d = probe.shape[1] if probe.ndim == 2 else _FEAT_DIM
            out = np.empty((n_pad, d), dtype)
        if n_pad > n:
            out[n:] = _pad_feature_block(n_pad - n, out.shape[1])
        return out, reused, (n - reused if identities is not None else 0)

    def _hierarchical_solve(
        self, keys: list[str], node_order: list[str], cap, alive,
        cur_idx=None, move_cost: float = 0.0, move_w=None,
        coarse_g_init=None, mesh=None, whole: bool = False,
    ):
        """Two-level OT re-solve over hashed identity features.

        The flat-cost modes materialize (bucket x node_axis); this one stays
        O(n x (groups + group_size + feat)) so it scales past HBM limits
        (see :mod:`rio_tpu.parallel.hierarchical`). Reads ONLY the
        lock-snapshotted ``node_order``/``cap``/``alive`` — it runs in the
        solver thread, concurrent with directory mutations.

        ``cur_idx``/``move_cost`` carry the flat modes' stay-put semantics
        into feature space when a sinkhorn/scaling rebalance is routed here
        at scale: each seated object's feature is pulled ``move_cost``
        toward its current node's embedding (the same cache-warmth encoding
        AffinityTracker learns from traffic). The pull biases the plan and
        fences nothing (``_build_obj_feat``); that only capacity pressure —
        dead nodes, skew — moves anything is the caller's doing, which
        keeps the plan's loads and re-expresses them with the fewest moves
        (``_cancel_transit``). Native ``mode="hierarchical"``
        solves don't use it: there the tracker's learned features are the
        stickiness mechanism and double-counting would over-stick.

        ``coarse_g_init`` warm-starts the coarse group solve from a prior
        plan's potentials (delta path); used only when its length matches
        this solve's group count. Returns ``(assignment, g, coarse_g,
        conv)``: the flat node potentials are always None here (the
        two-level solve produces group potentials instead), ``coarse_g``
        is the coarse stage's (n_groups,) potentials — on the mesh paths
        the pmean across shards, replicated (each shard solves the same
        capacity proportions, so the mean is a valid seed) — and ``conv``
        is the convergence record (iterations, residual, warm ratio,
        chunk/device fan-out, per-chunk timings) SolveStats surfaces.
        Dispatch composes both scale mechanisms: ``mesh``'s devices (the
        caller's route says which mesh, if any) divide the rows first, then
        per-device chunking bounds what one body compiles (conv gains
        ``mode_suffix="+mesh_chunk"`` when both are active, surfaced in
        ``SolveStats.mode``). ``whole`` is the full re-solve's word that
        ``keys`` is the directory's snapshot (``_build_obj_feat``).
        """
        from ..parallel.hierarchical import hierarchical_assign

        # Solve over a COMPACT node axis (real nodes padded to a group
        # multiple), not the full static axis: trailing all-dead groups
        # would concentrate coarse quotas into the few live groups and
        # overflow their buckets.
        m_real = max(1, len(node_order))
        group_size = 8
        m = -(-m_real // group_size) * group_size
        n_groups = m // group_size
        cap_full = np.asarray(cap, np.float32)
        alive_full = np.asarray(alive, np.float32)
        cap_np = np.zeros((m,), np.float32)
        alive_np = np.zeros((m,), np.float32)
        cap_np[:m_real] = cap_full[:m_real]
        alive_np[:m_real] = alive_full[:m_real]
        # PAD THE OBJECT AXIS to a power-of-two bucket: every static shape
        # fed to the jitted solve must be drawn from a bounded set, or a
        # steadily-allocating cluster compiles a FRESH executable per
        # rebalance and the jit cache grows without bound (found by the r5
        # endurance soak: ~25 MB of retained lowering/executable per new
        # directory size; ~1 GB/hour under continuous allocation). Pad
        # rows ride the solve as ordinary rows — they spread ~evenly under
        # the capacity marginals, costing only rounding-noise balance (the
        # real rows' per-node counts stay proportional) — and are sliced
        # off before the result leaves this function. The feature hook is
        # given ONLY real directory keys (its documented contract); pad
        # features come from a cached internal block.
        n = len(keys)
        bucket_n = _next_bucket(n)
        # Bucket from the fullest group's capacity share (host-side, static
        # per solve): uniform N/G sizing under-provisions skewed clusters.
        # Quantized to a power of two for the same bounded-compile reason
        # as the object axis (a continuous float share would otherwise
        # mint a fresh static `bucket` per capacity/liveness change).
        live_cap = (cap_np * alive_np).reshape(n_groups, group_size).sum(axis=1)
        share = live_cap.max() / max(live_cap.sum(), 1e-9)
        # Chunk the object axis above _HIER_CHUNK_ROWS — on BOTH paths.
        # The TPU backend's compile is superlinear in the flat row count
        # (v5e: 50 s at 655k, 599 s at 2.6M), and a mesh only divides the
        # rows by the device count before each shard compiles its flat
        # body, hitting the same wall one octave later. So devices divide
        # first (n_pad -> per_dev), then lax.map chunking bounds what one
        # body actually compiles at: mesh and chunks COMPOSE
        # (mesh_chunked_hierarchical_assign) instead of excluding each
        # other. Doubling n_chunks while halves stay exact keeps every
        # shape static for any po2 bucket and chunk-row override.
        n_shards = 1 if mesh is None else int(mesh.devices.size)
        n_pad = -(-bucket_n // n_shards) * n_shards
        per_dev = n_pad // n_shards
        n_chunks = 1
        while (
            per_dev // n_chunks > _HIER_CHUNK_ROWS
            and (per_dev // n_chunks) % 2 == 0
        ):
            n_chunks *= 2
        # Fine-stage bucket sized from PER-CELL rows: each (device, chunk)
        # cell solves 1/(n_shards*n_chunks) of the population against the
        # same fraction of every node's capacity.
        rows_cell = per_dev // n_chunks
        bucket_sz = _next_bucket(
            max(8, int(1.3 * rows_cell * float(share))), minimum=8
        )

        with stage("solve.features"):
            obj_feat, rows_reused, rows_hashed = self._build_obj_feat(
                keys, n_pad, node_order, cur_idx, move_cost, move_w, whole
            )
        d_feat = obj_feat.shape[1]
        node_feat = np.zeros((d_feat, m), np.float32)
        if node_order:
            nf = np.asarray(self._node_features(node_order), np.float32)
            assert nf.shape[1] == d_feat, (
                f"node feature dim {nf.shape[1]} != object feature dim {d_feat}"
            )
            if not np.isfinite(nf).all():
                # Same defensive sanitize as the object side: a garbage
                # embedding must never poison the cost (copy-on-write —
                # the hook may have handed us its internal buffer).
                nf = np.nan_to_num(nf, nan=0.0, posinf=0.0, neginf=0.0)
            node_feat[:, : len(node_order)] = nf.T
        kw = dict(
            n_groups=n_groups,
            bucket=min(bucket_sz, rows_cell),
            eps=self._eps,
            coarse_iters=self._n_iters,
            fine_iters=self._n_iters,
        )
        # Warm coarse seed from the previous plan — only when the group
        # axis still matches (axis growth / group-count drift means the
        # cached potentials describe a different problem: cold-start).
        # Cold start IS the zero seed (g0 = 0 in both solver forms), so
        # always pass an array: a None-vs-array flip would otherwise mint
        # a second jit trace for the exact same computation.
        if coarse_g_init is None or (
            np.asarray(coarse_g_init).shape != (n_groups,)
        ):
            warm_ratio = 0.0  # cold start (no / mismatched prior seed)
            coarse_g_init = np.zeros((n_groups,), np.float32)
        else:
            warm_ratio = _seed_warm_ratio(coarse_g_init)
        conv: dict = {
            "solver_iters": 2 * self._n_iters,  # coarse + fine stages
            "warm_ratio": warm_ratio,
            "chunks": n_chunks,
            "devices": n_shards,
            "feat_rows_reused": rows_reused,
            "feat_rows_hashed": rows_hashed,
        }
        if mesh is not None:
            # Shard the object axis across the mesh (the tier this mode is
            # for); obj_feat was built at n_pad (a shard multiple) so every
            # device gets per_dev rows, and the caller's [:n] slice drops
            # the pad. The warm seed threads through shard_map (it used to
            # be dropped here — PlanState potentials on the mesh path were
            # write-only) and comes back pmean'd for the next plan.
            # obj_feat goes in as the HOST block: _mesh_inputs / the timed
            # twin put each device's rows straight onto that device (a
            # jnp.asarray here would commit all of it to device 0 first).
            from ..parallel import hierarchical as _hier

            if n_chunks > 1:
                # The composed path: lax.map-chunked body INSIDE each
                # shard, one compile at the (rows_cell, d) cell shape.
                conv["mode_suffix"] = "+mesh_chunk"
                if os.environ.get("RIO_TPU_CHUNK_TIMING", "1") != "0":
                    res, chunk_ms = _hier.mesh_chunked_hierarchical_assign_timed(
                        mesh, obj_feat,
                        jnp.asarray(node_feat),
                        jnp.asarray(cap_np), jnp.asarray(alive_np),
                        n_chunks=n_chunks,
                        coarse_g_init=jnp.asarray(coarse_g_init),
                        **kw,
                    )
                    conv["chunk_ms"] = chunk_ms
                else:
                    res = _hier.mesh_chunked_hierarchical_assign(
                        mesh, obj_feat,
                        jnp.asarray(node_feat),
                        jnp.asarray(cap_np), jnp.asarray(alive_np),
                        n_chunks=n_chunks,
                        coarse_g_init=jnp.asarray(coarse_g_init),
                        **kw,
                    )
            else:
                res = _hier.sharded_hierarchical_assign(
                    mesh, obj_feat, jnp.asarray(node_feat),
                    jnp.asarray(cap_np), jnp.asarray(alive_np),
                    coarse_g_init=jnp.asarray(coarse_g_init),
                    **kw,
                )
        elif n_chunks > 1:
            from ..parallel import hierarchical as _hier

            if os.environ.get("RIO_TPU_CHUNK_TIMING", "1") != "0":
                # Host-looped twin: same jitted chunk body (compile stays
                # pinned to the chunk shape), but each chunk's
                # dispatch+block cycle is timed — the per-chunk signal
                # the hierarchical-ladder telemetry needs. Set
                # RIO_TPU_CHUNK_TIMING=0 to keep the single-executable
                # lax.map form instead.
                res, chunk_ms = _hier.chunked_hierarchical_assign_timed(
                    obj_feat, jnp.asarray(node_feat),
                    jnp.asarray(cap_np), jnp.asarray(alive_np),
                    n_chunks=n_chunks,
                    coarse_g_init=jnp.asarray(coarse_g_init),
                    **kw,
                )
                conv["chunk_ms"] = chunk_ms
            else:
                res = _hier.chunked_hierarchical_assign(
                    obj_feat, jnp.asarray(node_feat),
                    jnp.asarray(cap_np), jnp.asarray(alive_np),
                    n_chunks=n_chunks,
                    coarse_g_init=jnp.asarray(coarse_g_init),
                    **kw,
                )
        else:
            res = hierarchical_assign(
                obj_feat, jnp.asarray(node_feat),
                jnp.asarray(cap_np), jnp.asarray(alive_np),
                coarse_g_init=jnp.asarray(coarse_g_init),
                **kw,
            )
        coarse_g = (
            None if res.coarse_g is None else np.asarray(res.coarse_g, np.float32)
        )
        if res.coarse_err is not None:
            # Scalar pull AFTER the solve, never per iteration.
            conv["residual"] = float(np.asarray(res.coarse_err))
        return res.assignment[:n], None, coarse_g, conv

    # ---------------------------------------------------- incremental solve
    def _delta_gates_ok(self, plan: PlanState | None, force: bool) -> bool:
        """Delta-eligibility gates shared by both delta paths: a plan must
        exist; ``force`` overrides everything else (threshold disabled,
        plan marked stale by the transport-cost audit, staleness bound of
        ``max_delta_solves`` consecutive deltas)."""
        if plan is None:
            return False
        if force:
            return True
        if self._delta_threshold <= 0.0 or plan.stale:
            return False
        return plan.delta_solves < self._max_delta_solves

    def _class_refresh(self, load, cap, alive, counts_np, cap_alive, mode, plan):
        """Warm potential refresh at the STATIC class shape (M x M): the
        same collapse the full path exploits, seeded with the plan's
        potentials so a handful of iterations re-converges after one
        liveness flip. No N dependence -> no per-event recompile, one
        cached executable per node axis (see ``_class_refresh_device``).
        Returns ``(g, score, err)`` — the new column potentials, the
        per-node host fill score, and the refresh's scalar convergence
        residual. A missing seed is passed as zeros, not
        None: cold start IS the zero seed in both solver forms, and a
        None-vs-array flip would mint a second trace."""
        with stage("solve.delta.refresh"):
            base = build_cost_matrix(jnp.zeros_like(load), cap, alive)[0]
            g_seed = (
                jnp.zeros((base.shape[0],), jnp.float32)
                if plan.g is None
                else jnp.asarray(plan.g)
            )
            g_r, err = _class_refresh_device(
                base,
                jnp.asarray(np.asarray(counts_np, np.float32)),
                jnp.asarray(cap_alive.astype(np.float32)),
                g_seed,
                mode=mode,
                move_cost=self._move_cost,
                eps=min(
                    self._eps,
                    self._move_cost / 25.0 if self._move_cost > 0 else self._eps,
                ),
                n_iters=max(4, min(8, self._n_iters)),
            )
            g_np = np.asarray(g_r, np.float64)
            score = np.asarray(base, np.float64) - np.where(
                np.isfinite(g_np), g_np, -1e30
            )
            return g_r, score, float(np.asarray(err))

    def _delta_fast_snapshot(self, plan, n, cap, alive, force):
        """O(displaced) delta snapshot, taken under the provider lock.

        The dominant per-event host cost of a churn rebalance at directory
        scale is not the solve — it is materializing the O(N) key/seat
        array snapshot (~0.045 s per million objects on the chip host, on
        the loop and under the lock). For the dominant
        churn shape — nodes LEAVING the schedulable set with every
        survivor at or under its integer fair quota — the displaced set is
        exactly the departed nodes' seats, which ``_by_node`` already
        holds. This helper detects that shape in O(M) and snapshots just
        the displaced ``(key, old_index)`` pairs, so the whole event costs
        O(displaced + M^2) instead of O(N).

        A survivor over its integer quota (a node RETURNED, or a load
        derate shrank a node's share) sheds its overflow here too: under the
        flat cost model its rows are interchangeable, so the newest
        ``counts - quota`` of ``_by_node`` are as good an overflow as any
        rank. (Routed through the array snapshot, every re-priced node cost
        a churning cluster an O(N) hold of the loop per event.)

        Returns None whenever per-seat decisions could matter — with an
        ``object_costs`` hook an over-quota survivor needs rank-based
        eviction (hot objects are kept); the array-snapshot delta / full
        pipeline handles that. With no survivor over quota there are no
        evictions, so prices cannot change which objects move, and the flat
        cost model prices every destination identically for all objects.
        """
        if not self._delta_gates_ok(plan, force):
            return None
        cap_np = np.asarray(cap, np.float64)
        alive_np = np.asarray(alive, np.float64)
        cap_alive = cap_np * (alive_np > 0)
        m = cap_alive.shape[0]
        sched = cap_alive > 0.0
        counts = np.zeros(m, np.int64)
        for j, seats in self._by_node.items():
            if j < m:
                counts[j] = len(seats)
        quota = integer_fair_quotas(cap_alive, n, counts)
        over_nodes = np.nonzero(sched & (counts > quota))[0]
        if over_nodes.size and self._object_costs is not None:
            return None  # priced over-quota eviction: needs per-seat ranks
        disp_nodes = np.nonzero(~sched & (counts > 0))[0]
        retained = np.where(sched, np.minimum(counts, quota), 0)
        d = int(counts[disp_nodes].sum() + (counts - quota)[over_nodes].sum())
        if not force and d > self._delta_threshold * n:
            return None
        disp: list[tuple[str, int]] = []
        for j in disp_nodes.tolist():
            disp.extend((k, j) for k in self._by_node.get(j, ()))
        for j in over_nodes.tolist():
            seats = self._by_node[j]
            newest = reversed(seats) if hasattr(seats, "__reversed__") else iter(seats)
            disp.extend(
                (k, j) for k in itertools.islice(newest, int(counts[j] - quota[j]))
            )
        residual = quota - retained
        return {
            "disp": disp,
            "counts": counts,
            "cap_alive": cap_alive,
            "quota": quota,
            "retained": retained,
            "residual": residual,
            "d": d,
        }

    async def _delta_fast_rebalance(
        self, fast, *, n, mode, move_sink, load, cap, alive,
        node_order, plan, snapshot_epoch,
    ) -> int:
        """Solve + commit an O(displaced) fast delta (see
        :meth:`_delta_fast_snapshot`). Same thread/epoch discipline as the
        array pipeline: device work off the event loop, epoch re-checked
        under the lock before apply, discarded attempts recorded."""
        from ..tracing import span

        solved_as = f"{mode}+delta"
        disp = fast["disp"]
        d = fast["d"]
        residual = fast["residual"]
        cap_alive = fast["cap_alive"]
        quota = fast["quota"]
        retained = fast["retained"]
        m = cap_alive.shape[0]
        sched = cap_alive > 0.0

        def _solve():
            c0 = _compile_seconds()
            with stage("solve.device") as st, span(
                "placement_solve", mode=solved_as, n=n
            ), stage("solve.delta"):
                g_new = None
                coarse_new = None
                conv: dict = {}
                if d == 0:
                    # Nothing displaced (pure load jitter): the plan stands.
                    fill = np.zeros((0,), np.int32)
                elif mode == "hierarchical":
                    # Displaced keys through the two-level solve against
                    # the residual columns (chunk-shape compile bound).
                    res_cap = residual.astype(np.float32)
                    res_alive = (residual > 0).astype(np.float32)
                    fill, _, coarse_new, conv = self._hierarchical_solve(
                        [k for k, _ in disp], node_order, res_cap,
                        res_alive, coarse_g_init=plan.coarse_g,
                        mesh=self._mesh,
                    )
                    fill = _route_unseatable(
                        np.asarray(fill, np.int32), len(node_order), load,
                        res_alive, res_cap,
                    )
                else:
                    if mode in ("sinkhorn", "scaling"):
                        g_new, score, ref_err = self._class_refresh(
                            load, cap, alive, fast["counts"], cap_alive,
                            mode, plan,
                        )
                        conv = {
                            "solver_iters": max(4, min(8, self._n_iters)),
                            "residual": ref_err,
                            "warm_ratio": _seed_warm_ratio(plan.g),
                        }
                    else:
                        score = np.where(
                            sched, retained / np.maximum(quota, 1), 1e18
                        )
                    with stage("solve.delta.fill"):
                        fill = residual_capacity_assign(score, residual)
                # Transport-cost audit (see _delta_solve): achieved
                # seating vs the integer-quota ideal; a tripped audit
                # marks the plan stale so the NEXT solve goes full.
                counts_after = (
                    retained + np.bincount(fill, minlength=m)
                ).astype(np.float64)
                safe_cap = np.maximum(cap_alive, 1e-9)
                num = float(np.sum(counts_after**2 / safe_cap))
                den = float(np.sum(quota.astype(np.float64) ** 2 / safe_cap))
                stale = bool(
                    den > 0.0 and num > self._delta_audit_ratio * den
                )
            conv = _conv_timing(conv, st.ms, c0)
            return fill, g_new, coarse_new, st.ms, stale, counts_after, conv

        fill, g, coarse_g, solve_ms, stale, counts_after, conv = (
            await asyncio.to_thread(_solve)
        )

        async with self._lock:
            if self._epoch != snapshot_epoch:
                self.stats = SolveStats(
                    n_objects=n,
                    n_nodes=len(self._node_order),
                    solve_ms=solve_ms,
                    displaced=d,
                    epoch=self._epoch,
                    mode=solved_as,
                    discarded=True,
                    history=self._archived_history(),
                    **_conv_fields(conv),
                )
                return 0
            hist = self._archived_history()
            with stage("solve.apply") as st_apply:
                moved = 0
                planned: list[tuple[str, str, str]] = []
                for (key, old_idx), new_idx in zip(disp, fill.tolist()):
                    if move_sink is not None:
                        planned.append(
                            (key, node_order[old_idx], node_order[int(new_idx)])
                        )
                    elif self._set_placement(key, int(new_idx)):
                        moved += 1
                if move_sink is not None:
                    moved = len(planned)
                self._delta_displaced += d
                self._delta_moved += moved
                if g is not None:
                    self._g = g
                    self._g_fp = self._sched_fp()
                self._recount_loads()
                self._epoch += 1
                self._plan = PlanState(
                    g=g if g is not None else plan.g,
                    coarse_g=coarse_g if coarse_g is not None else plan.coarse_g,
                    seat_counts=np.asarray(counts_after, np.int64),
                    epoch=self._epoch,
                    liveness_fp=self._sched_fp(),
                    delta_solves=plan.delta_solves + 1,
                    stale=stale,
                )
            self.stats = SolveStats(
                n_objects=n,
                n_nodes=len(self._node_order),
                solve_ms=solve_ms,
                apply_ms=st_apply.ms,
                moved=moved,
                displaced=d,
                epoch=self._epoch,
                mode=solved_as,
                discarded=False,
                history=hist,
                **_conv_fields(conv),
            )
        if planned:
            with stage("solve.moves"):
                planned.sort(key=lambda mv: (mv[1], mv[2]))
            # Outside the lock on purpose: handoffs call back into
            # update()/lookup(), which take it.
            await move_sink(planned)
        return moved

    def _delta_solve(
        self, keys, cur_idx, load, cap, alive, n_real, node_order,
        plan: PlanState, mode: str, obj_w, force: bool,
    ):
        """Delta rebalance: re-solve ONLY the displaced objects against
        residual capacity, warm-starting from the previous plan.

        The displaced set is (a) every seat on a node that left the
        schedulable set (dead / cordoned / capacity-zero) plus (b) the
        over-quota overflow on surviving nodes (per-seat rank beyond the
        node's integer fair quota). Undisplaced objects keep their seats
        BY CONSTRUCTION — they are never re-solved — and the displaced
        fill targets each node's residual quota (quota minus retained
        seats), so the result lands on exactly the same integer per-node
        counts a full quota-repaired solve would produce. One churn event
        then costs O(N) host work + an O(M^2) warm potential refresh,
        not an O(N x M) directory solve.

        Runs in the solver thread over loop-side snapshots only (the
        provider's standard discipline); reads nothing live but immutable
        config. Returns ``(assignment, g, coarse_g, displaced, stale,
        conv)`` — ``conv`` is the convergence record SolveStats surfaces —
        or None when a gate says this event needs the full solve:
        no plan / plan marked stale / ``max_delta_solves`` consecutive
        deltas exceeded / displaced fraction above ``delta_threshold``
        (``force`` overrides every gate except a missing plan).
        """
        n = len(keys)
        if n == 0 or not self._delta_gates_ok(plan, force):
            return None
        # A delta that runs to a result is one ``solve.delta`` record, logged
        # at the return (a gate below may still hand the event to the full
        # solve), beside ``solve.delta.refresh`` and ``solve.delta.fill``.
        t_delta = time.perf_counter_ns()
        cap_np = np.asarray(cap, np.float64)
        alive_np = np.asarray(alive, np.float64)
        cap_alive = cap_np * (alive_np > 0)
        m = cap_alive.shape[0]
        sched = cap_alive > 0.0
        cur = np.asarray(cur_idx, np.int64)
        # (m,), sums to n exactly; a tied unit stays on the fuller node.
        quota = integer_fair_quotas(cap_alive, n, np.bincount(cur, minlength=m))
        # Rank each object within its current seat's population (one
        # stable sort — the host analog of ops.assignment.rank_within_group).
        # With per-object move prices the heavy/hot objects rank first and
        # are kept, so quota pressure evicts cold objects — mirroring the
        # dense path's scaled stay-put discount.
        if obj_w is not None:
            order = np.lexsort((-np.asarray(obj_w, np.float64), cur))
        else:
            order = np.argsort(cur, kind="stable")
        sorted_seats = cur[order]
        starts = np.searchsorted(sorted_seats, np.arange(m))
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n) - starts[sorted_seats]
        keep = sched[cur] & (rank < quota[cur])
        disp_pos = np.nonzero(~keep)[0]
        d = int(disp_pos.shape[0])
        if d == 0:
            # Nothing displaced (e.g. a node RETURNED): the plan stands.
            stage_between("solve.delta", t_delta, time.perf_counter_ns())
            return cur.astype(np.int32), None, None, 0, False, {}
        if not force and d > self._delta_threshold * n:
            return None
        # retained[j] = min(counts[j], quota[j]) on schedulable nodes, 0
        # elsewhere; residual >= 0 and sums to d exactly (quota sums to n,
        # retained to n - d).
        retained = np.bincount(cur[keep], minlength=m)
        residual = quota - retained

        g_new = None
        coarse_new = None
        conv: dict = {}
        if mode == "hierarchical":
            # Route the displaced keys through the two-level solve against
            # the residual capacity columns — the chunked dispatch inside
            # keeps any displaced count compile-bounded at the chunk shape.
            disp_keys = [keys[i] for i in disp_pos.tolist()]
            res_cap = residual.astype(np.float32)
            res_alive = (residual > 0).astype(np.float32)
            fill, _, coarse_new, conv = self._hierarchical_solve(
                disp_keys, node_order, res_cap, res_alive,
                coarse_g_init=plan.coarse_g, mesh=self._mesh,
            )
            fill = _route_unseatable(
                np.asarray(fill, np.int32), n_real, load, res_alive, res_cap
            )
        else:
            if mode in ("sinkhorn", "scaling"):
                # Warm M x M potential refresh (see _class_refresh).
                g_new, score, ref_err = self._class_refresh(
                    load, cap, alive, np.bincount(cur, minlength=m),
                    cap_alive, mode, plan,
                )
                conv = {
                    "solver_iters": max(4, min(8, self._n_iters)),
                    "residual": ref_err,
                    "warm_ratio": _seed_warm_ratio(plan.g),
                }
            else:
                # Greedy has no potentials: order nodes by how full their
                # retained population already is. Every feasible fill hits
                # the same per-node counts (residual is integer-exact), so
                # the score only decides WHICH interchangeable seat runs
                # land where.
                score = np.where(
                    sched, retained / np.maximum(quota, 1), 1e18
                )
            with stage("solve.delta.fill"):
                fill = residual_capacity_assign(score, residual)
        out = cur.astype(np.int32).copy()
        out[disp_pos] = fill

        # Transport-cost audit (quadratic congestion proxy): compare the
        # achieved per-node seating against the ideal integer quotas. The
        # flat fills hit the quotas exactly (ratio 1.0 by construction);
        # the hierarchical fill is capacity-proportional per group, and
        # repeated deltas can drift — a tripped audit marks the plan stale
        # so the NEXT solve goes full. Unschedulable nodes get a tiny
        # capacity floor, so any stray seat there blows the ratio up and
        # forces the full solve — exactly the right reaction.
        counts_after = np.bincount(out, minlength=m).astype(np.float64)
        safe_cap = np.maximum(cap_alive, 1e-9)
        num = float(np.sum(counts_after**2 / safe_cap))
        den = float(np.sum(quota.astype(np.float64) ** 2 / safe_cap))
        stale = bool(den > 0.0 and num > self._delta_audit_ratio * den)
        stage_between("solve.delta", t_delta, time.perf_counter_ns())
        return out, g_new, coarse_new, d, stale, conv

    # ------------------------------------------------ communication graph
    def set_edge_graph(self, rows) -> int:
        """Install the cluster-merged communication graph.

        ``rows`` is the ``merge_edges`` shape (``[src, dst, bytes_per_s,
        calls_per_s, local_frac]``, extra columns optional). Edges from
        external clients (``src == "client"``) are dropped — a client
        cannot be co-located, so attraction toward its traffic is
        meaningless — as are self-edges and zero-rate rows. The remaining
        edges are symmetrized (undirected key, rates summed), weighted as
        bytes/s plus a per-call framing overhead, and normalized so the
        heaviest edge is 1.0: the affinity_weight knob then has one unit
        regardless of absolute traffic volume. Returns the edge count;
        atomic swap, safe against a concurrent solver-thread read."""
        edges: dict[tuple[str, str], float] = {}
        for r in rows or ():
            src, dst = str(r[0]), str(r[1])
            if src == "client" or src == dst:
                continue
            bps = max(0.0, float(r[2]))
            cps = max(0.0, float(r[3])) if len(r) > 3 else 0.0
            # ~64 B of frame/header cost per call keeps pure-call-count
            # chatter (tiny payloads, high rate) visible in the weight.
            w = bps + 64.0 * cps
            if w <= 0.0:
                continue
            key = (src, dst) if src < dst else (dst, src)
            edges[key] = edges.get(key, 0.0) + w
        if edges:
            top = max(edges.values())
            edges = {k: v / top for k, v in edges.items()}
        self._edge_graph = edges
        return len(edges)

    def _affinity_refine(self, keys, assignment, node_order, cap, alive):
        """Alternating linearized OT refinement over the edge graph.

        Runs in the solver thread after a FULL solve. Each pass linearizes
        the quadratic co-location objective around the current assignment:
        an object's attraction to node ``a`` is the edge-weighted sum of
        ``Hfac[a, seat(neighbor)]`` (1.0 same worker, host_factor same
        host, 0.0 cross-host), folded into the per-object cost row as a
        discount — so the unchanged Sinkhorn core (per-row gauge shift,
        warm starts) solves it like any other dense problem. Only the
        edge-touching subset is re-solved (capped at
        ``_AFFINITY_MAX_ROWS`` heaviest, padded to a power-of-2 bucket for
        compile reuse); everything else keeps its balance-optimal seat. A
        pass is accepted only if BOTH the edge-cut transport cost and the
        total objective (capacity overflow + weighted cut) are
        non-increasing — the monotonicity the invariant tests pin.

        Returns the refined assignment (np.int32, length n) or None when
        the graph doesn't touch this directory / no pass was accepted.
        """
        edges = self._edge_graph  # atomic snapshot
        w_aff = self._affinity_weight
        n = len(keys)
        key_ix = {k: i for i, k in enumerate(keys)}
        ei: list[int] = []
        ej: list[int] = []
        ew: list[float] = []
        for (a, b), w in edges.items():
            ia = key_ix.get(a)
            ib = key_ix.get(b)
            if ia is None or ib is None:
                continue
            ei.append(ia)
            ej.append(ib)
            ew.append(w)
        if not ei:
            return None
        # Symmetrize into directed arrays (each undirected edge twice) so
        # one scatter-add accumulates every object's full neighborhood.
        e_src = np.asarray(ei + ej, np.int64)
        e_dst = np.asarray(ej + ei, np.int64)
        e_w = np.asarray(ew + ew, np.float32)

        cap_np = np.asarray(cap, np.float32)
        alive_np = np.asarray(alive, np.float32)
        m = cap_np.shape[0]
        # Same-host structure: address up to the ":port" suffix identifies
        # the host; padded (unregistered) columns get unique tokens so the
        # host mask degenerates to the identity there.
        hosts = [
            node_order[i].rsplit(":", 1)[0] if i < len(node_order) else f"\x00pad{i}"
            for i in range(m)
        ]
        host_id = np.asarray(
            [list(dict.fromkeys(hosts)).index(h) for h in hosts], np.int64
        )
        hf = self._affinity_host_factor
        same_host = (host_id[:, None] == host_id[None, :]).astype(np.float32)
        hfac = hf * same_host
        np.fill_diagonal(hfac, 1.0)
        dist = 1.0 - hfac  # 0 same worker / (1-hf) same host / 1 cross

        # Edge-touching subset, heaviest first when over the row cap.
        deg = np.zeros((n,), np.float32)
        np.add.at(deg, e_src, e_w)
        sub = np.nonzero(deg > 0.0)[0]
        if sub.size > _AFFINITY_MAX_ROWS:
            sub = sub[np.argsort(-deg[sub], kind="stable")[:_AFFINITY_MAX_ROWS]]
            sub = np.sort(sub)
        s = int(sub.size)
        pos = np.full((n,), -1, np.int64)
        pos[sub] = np.arange(s)
        in_sub = pos[e_src] >= 0
        # Per-pass edge orientation. A simultaneous (Jacobi) update lets a
        # chatty pair SWAP seats forever — each endpoint chases the
        # other's pre-pass seat — so every pass anchors one endpoint per
        # edge: even passes move the lighter-degree endpoint toward the
        # heavier one (satellites join planets), odd passes reverse the
        # orientation so anchors catch up to moved satellites. Degree
        # ties break by index, keeping the orientation a strict total
        # order per edge.
        lighter = (deg[e_src] < deg[e_dst]) | (
            (deg[e_src] == deg[e_dst]) & (e_src < e_dst)
        )

        # Balance base row (identical for every object, exactly the dense
        # solve's cost model) and fair shares: each pass gives the mobile
        # half a slackened residual capacity per node — what the slack-
        # padded fair share leaves after every frozen seat is counted.
        # The +1 covers integer granularity at small fair shares (with 2
        # objects per node a 1.25x slack is less than one whole object,
        # and no pair could ever co-locate).
        base = np.asarray(
            build_cost_matrix(jnp.zeros((m,), jnp.float32), cap, alive),
            np.float32,
        ).reshape(-1, m)[0]
        cap_alive = cap_np * alive_np
        fair = cap_alive / max(float(np.sum(cap_alive)), 1e-30) * n
        slack_cap = fair * self._affinity_slack + 1.0
        schedulable = (cap_alive > 0.0).astype(np.float64)

        total_w = float(np.sum(e_w))

        def _cut(seats: np.ndarray) -> float:
            return float(np.sum(e_w * dist[seats[e_src], seats[e_dst]])) / max(
                total_w, 1e-30
            )

        def _total(seats: np.ndarray) -> float:
            counts = np.bincount(seats, minlength=m)
            overflow = float(np.sum(np.maximum(counts - slack_cap, 0.0))) / n
            return overflow + w_aff * _cut(seats)

        seats = np.asarray(assignment, np.int32).copy()
        history = [
            {"pass": 0, "cut": _cut(seats), "total": _total(seats), "accepted": True}
        ]
        g_warm = None
        accepted_any = False
        for p in range(self._affinity_passes):
            mask = in_sub & (lighter if p % 2 == 0 else ~lighter)
            if not np.any(mask):
                continue
            # Only the mobile endpoints are re-solved this pass; anchors
            # and everything outside the subset are frozen — their seats
            # consume capacity but cannot be displaced (the failure mode
            # of re-solving anchors is capacity pressure pushing them off
            # the very seats their satellites are converging toward).
            mobile = np.unique(e_src[mask])
            sp = int(mobile.size)
            pos_p = np.full((n,), -1, np.int64)
            pos_p[mobile] = np.arange(sp)
            attract = np.zeros((sp, m), np.float32)
            np.add.at(
                attract,
                pos_p[e_src[mask]],
                e_w[mask, None] * hfac[seats[e_dst[mask]]],
            )
            cost = np.broadcast_to(base, (sp, m)).copy()
            cost -= w_aff * attract
            # Stay-put discount at the object's current seat: a refine
            # move still pays the state handoff.
            cost[np.arange(sp), seats[mobile]] -= self._move_cost
            frozen = np.bincount(seats, minlength=m).astype(np.float64)
            frozen -= np.bincount(seats[mobile], minlength=m)
            col_cap = np.maximum(slack_cap - frozen, 0.0) * schedulable
            bucket = _next_bucket(sp)
            mass = np.zeros((bucket,), np.float32)
            mass[:sp] = 1.0
            cost_p = np.zeros((bucket, m), np.float32)
            cost_p[:sp] = cost
            cost_j = jnp.asarray(cost_p)
            f, g, _err = sinkhorn(
                cost_j,
                jnp.asarray(mass),
                jnp.asarray(col_cap, jnp.float32),
                eps=self._eps,
                n_iters=self._n_iters,
                g_init=g_warm,
            )
            g_warm = g  # warm-start the next linearization
            new_seats = np.asarray(plan_rounded_assign(cost_j, f, g, self._eps))[
                :sp
            ]
            # Any row the rounded plan could not seat on a live column
            # keeps its current seat (mirrors _route_unseatable's intent
            # without re-pricing the frozen rows).
            old = seats[mobile]
            bad = (
                (new_seats < 0)
                | (new_seats >= m)
                | (alive_np[new_seats % m] <= 0.0)
            )
            new_seats = np.where(bad, old, new_seats).astype(np.int32)
            # Integer capacity enforcement: the rounded plan's per-row
            # argmax can overshoot a column (that's what _repair_exact
            # fixes on the main path). Movers INTO each node are ranked
            # by cost gain and truncated to the whole seats the residual
            # actually has; the rest keep their current seat.
            gain = (
                cost[np.arange(sp), old] - cost[np.arange(sp), new_seats]
            )
            stayers = np.bincount(old[new_seats == old], minlength=m)
            for c in np.unique(new_seats[new_seats != old]):
                movers = np.nonzero((new_seats == c) & (old != c))[0]
                allowed = int(max(0.0, np.floor(col_cap[c] - stayers[c])))
                if movers.size > allowed:
                    ranked = movers[np.argsort(-gain[movers], kind="stable")]
                    new_seats[ranked[allowed:]] = old[ranked[allowed:]]
            cand = seats.copy()
            cand[mobile] = new_seats
            c_cut, c_tot = _cut(cand), _total(cand)
            ok = (
                c_cut <= history[-1]["cut"] + 1e-9
                and c_tot <= history[-1]["total"] + 1e-9
            )
            history.append(
                {"pass": p + 1, "cut": c_cut, "total": c_tot, "accepted": ok}
            )
            if not ok:
                break
            if not np.array_equal(cand, seats):
                accepted_any = True
            seats = cand
        self._affinity_history = history  # atomic swap (tests/telemetry)
        return seats if accepted_any else None

    def _snapshot_head(self, delta: bool | None) -> tuple:
        """What every route of a solve reads first, in O(nodes) (lock
        held): the row count, the epoch, the node vectors, the plan, the
        seats a node and the O(displaced) fast path's verdict."""
        n = len(self._placements)
        snapshot_epoch = self._epoch
        self._recount_loads()
        load, cap, alive = self._node_vectors()
        node_order = list(self._node_order)  # snapshot for off-lock use
        no_capacity = self._no_schedulable_capacity_host()
        plan = self._plan  # immutable snapshot (atomic-swap field)
        # O(displaced) fast path FIRST: for pure node-departure churn the
        # displaced keys come straight from _by_node and the O(N) key/seat
        # snapshot — the dominant per-event host cost at directory scale —
        # is skipped entirely.
        fast = None
        if delta is not False and n and not no_capacity:
            fast = self._delta_fast_snapshot(
                plan, n, cap, alive, force=(delta is True)
            )
        # The seats a node, exact (``_recount_loads`` just read them off
        # the per-node index): what an O(N) solve's commit diff starts from.
        seats = None
        if fast is None and n:
            seats = np.zeros((self._node_axis,), np.intp)
            for s in self._nodes.values():
                seats[s.index] = int(s.load)
        return (
            n, snapshot_epoch, load, cap, alive, node_order, no_capacity,
            plan, seats, fast,
        )

    def _read_rows(
        self, rows: tuple, keys: list[str], cur_idx: np.ndarray, a: int, b: int
    ) -> None:
        """Rows ``a..b`` of the directory, its own key objects and their
        seats, from the two running iterators ``rows`` (lock held; the one
        place that knows how the mirror keeps keys and seats).

        ``values()`` iterates in ``keys()`` order (insertion order) and
        skips the per-key hash lookup a genexpr would pay: ~0.045 s a
        million rows on the chip host (~0.35 s as a genexpr), nothing per
        key in Python. An iterator of a dict that changed size raises
        ``RuntimeError``.
        """
        key_iter, seat_iter = rows
        keys.extend(itertools.islice(key_iter, b - a))
        # (``count`` stops the read: the iterator is left at row ``b``.)
        cur_idx[a:b] = np.fromiter(seat_iter, np.int32, count=b - a)

    async def _snapshot(self, delta: bool | None) -> tuple:
        """A solve's snapshot of the directory, a consistent cut at ONE
        epoch: ``_snapshot_head``'s fields, then ``keys`` and ``cur_idx``
        (both None for an empty directory and for the fast path).

        The O(N) read of a full solve (or of the O(N) delta) was one hold
        of the servers' loop under the directory's lock, ~190 ms at
        4,194,304 rows, and every heartbeat due inside it waited for it. A
        directory of more than two slices is therefore read
        ``_SNAPSHOT_SLICE_ROWS`` rows a hold, ONE pass of one running
        iterator each over the keys and the seats; between two slices the
        lock is released and the loop turns once (due requests, the
        monitors' ticks, a waiting writer). Every writer of ``_placements``
        moves ``_epoch`` under the lock, so a slice is read only while the
        epoch and the row count are the first slice's: then nobody wrote
        since, and the slices are the cut one hold would have read.
        Otherwise the read RESTARTS at the new epoch, from the head (a
        writer may have opened the fast path). After two restarts the third
        read takes the whole directory in one hold: a directory under
        steady writes still gets its plan, at what it paid before.
        """
        step = _SNAPSHOT_SLICE_ROWS
        restarts = 0
        while True:
            async with self._lock:
                t0 = time.perf_counter_ns()
                head = self._snapshot_head(delta)
                n, snapshot_epoch, *_, fast = head
                if fast is not None or not n:
                    return (*head, None, None)
                rows = iter(self._placements), iter(self._placements.values())
                keys: list[str] = []
                cur_idx = np.empty((n,), np.int32)
                if n <= 2 * step or restarts == 2:
                    self._read_rows(rows, keys, cur_idx, 0, n)
                    if restarts == 2:
                        self._snapshot_whole += 1
                    return (*head, keys, cur_idx)
                self._read_rows(rows, keys, cur_idx, 0, step)
                self._snapshot_slices += 1
                self._snapshot_busy_ns += time.perf_counter_ns() - t0
            for a in range(step, n, step):
                await asyncio.sleep(0)  # one turn of the loop, lock released
                async with self._lock:
                    if self._epoch != snapshot_epoch or len(self._placements) != n:
                        break
                    t0 = time.perf_counter_ns()
                    try:
                        self._read_rows(rows, keys, cur_idx, a, min(n, a + step))
                    except RuntimeError:  # the dict changed size under its iterator
                        break
                    self._snapshot_slices += 1
                    self._snapshot_busy_ns += time.perf_counter_ns() - t0
            else:
                self._snapshot_sliced += 1
                return (*head, keys, cur_idx)
            restarts += 1
            self._snapshot_restarts += 1

    async def rebalance(
        self,
        *,
        mode: str | None = None,
        move_sink=None,
        delta: bool | None = None,
    ) -> int:
        """Re-solve the directory; returns number of moves.

        By default (``delta=None``) a churn event first attempts the
        incremental **delta** path (:meth:`_delta_solve`): only displaced +
        new objects are re-solved against residual capacity with
        warm-started potentials, and the full-directory solve runs only
        when a delta gate trips (no/stale plan, displaced fraction over
        ``delta_threshold``, ``max_delta_solves`` staleness bound, or the
        transport-cost audit). ``delta=False`` forces the full solve;
        ``delta=True`` forces the delta path whenever a plan exists
        (overriding threshold and staleness). ``stats.mode`` reports which
        path ran (``"<mode>+delta"`` for an incremental solve).

        Snapshots the epoch before the (async-yielding) device solve and
        discards the result if the directory changed underneath — the
        single-writer/versioned-epoch consistency design from ``SURVEY.md``
        §7 "hard parts". The snapshot (:meth:`_snapshot`) is a cut of keys
        and seats at that one epoch. A directory of more than two slices of
        ``_SNAPSHOT_SLICE_ROWS`` rows is read a slice a lock hold, and
        between two slices the event loop turns once, so requests, ticks
        and writers wait for a slice and not for the directory. A RESTART
        is what a writer between two slices costs: the epoch or the row
        count moved, what was read is dropped, and the read begins again
        at the new epoch; the third read of a call takes one hold.
        ``place_gauges()`` counts them (``rio.place.snapshot.*``).

        ``move_sink`` (``async (list[(key, from_addr, to_addr)]) -> int``)
        turns the apply phase from raw directory writes into *planned*
        moves: the solve commits (epoch bump, so sibling solves discard)
        but rows are left standing, and the sink — the migration
        coordinator — actuates each move as a coordinated handoff whose
        own ``update()`` flips the row. The sink runs OUTSIDE the
        provider lock: handoffs call back into ``update``/``lookup``.

        One call is one ``solve.full`` stage (whichever path it takes) with
        children ``solve.snapshot``, ``solve.device`` (and in it
        ``solve.features`` and ``solve.transit``: the plan's rows routed and
        committed with the fewest moves), ``solve.diff`` (the worker thread
        still: which rows move, the plan's seats a node and, where a sink
        takes a plan, the move list ordered for it, ``_commit_diff``) and
        ``solve.apply`` (the commit: the epoch check, then one lock hold of
        O(movers) and O(nodes); the O(displaced) route's own commit orders
        its moves under ``solve.moves``); ``SolveStats.solve_ms`` and
        ``apply_ms`` are the device and apply stages' own stamps.
        ``place_gauges()`` counts the diffs that were committed
        (``rio.solve.diff.*``).
        """
        with stage("solve.full"):
            return await self._rebalance(mode, move_sink, delta)

    async def _rebalance(self, mode: str | None, move_sink, delta: bool | None) -> int:
        self._place_compile_cache()
        # An explicit mode="auto" resolves exactly like the constructor
        # default (it would otherwise fall through every dispatch check
        # and silently run the greedy branch).
        mode = self._solver_mode() if mode in (None, "auto") else mode
        # ONE record a call, whatever the number of slices: its wall spans
        # the requests served between them (``rio.place.snapshot.busy_ms``
        # is the work).
        with stage("solve.snapshot"):
            (
                n, snapshot_epoch, load, cap, alive, node_order, no_capacity,
                plan, seats, fast, keys, cur_idx,
            ) = await self._snapshot(delta)
        if not n:
            self._kept_identities = None  # an empty directory keeps no rows
            return 0
        if fast is not None:
            return await self._delta_fast_rebalance(
                fast, n=n, mode=mode, move_sink=move_sink, load=load,
                cap=cap, alive=alive, node_order=node_order, plan=plan,
                snapshot_epoch=snapshot_epoch,
            )

        bucket = _next_bucket(n)
        def _solve() -> tuple:
            """Device solve off the event loop: np.asarray blocks until the
            TPU finishes, so running it in a thread keeps lookups/gossip/RPCs
            live — and makes the epoch-discard check below load-bearing.
            Only the snapshots taken under the lock are read here."""
            from ..tracing import span

            if no_capacity:
                # Zero schedulable capacity (all nodes dead/cordoned at
                # once): reshuffling seats among dead nodes is pure churn
                # and the degenerate waterfill/OT outputs are meaningless —
                # stay put until liveness returns, recorded as its own
                # mode (span included, so trace tooling sees the outage
                # mode next to its SolveStats entry).
                solved_as = f"{mode}+no_capacity"
                with span("placement_solve", mode=solved_as, n=n):
                    return cur_idx.copy(), None, None, solved_as, 0, False, None
            # Per-object move prices (object_costs hook; tracker-measured
            # request rates + snapshot bytes by default). Evaluated in the
            # solver thread — hooks must read only atomically-swapped
            # state, the contract AffinityTracker already follows. Any
            # hook failure or shape mismatch degrades to uniform pricing:
            # load telemetry must never break a rebalance.
            obj_w = None
            if self._object_costs is not None:
                with stage("solve.features"):
                    try:
                        w = np.asarray(self._object_costs(keys), np.float32)
                    except Exception:  # noqa: BLE001
                        w = None
                if w is not None and w.shape == (n,):
                    w = np.clip(np.nan_to_num(w, nan=1.0, posinf=1.0), 0.0, 1e6)
                    if n and float(np.ptp(w)) > 0.0:
                        obj_w = w
                    # Uniform weights are the scalar move_cost case:
                    # leave obj_w None and keep the collapsed fast path.
            # Incremental attempt FIRST: with a prior plan and bounded
            # displacement, the delta path replaces the whole directory
            # solve below. Falls through to the full solve (returning
            # None) when any gate trips.
            if delta is not False and plan is not None:
                with span("placement_solve", mode=f"{mode}+delta", n=n):
                    d_res = self._delta_solve(
                        keys, cur_idx, load, cap, alive,
                        len(node_order), node_order, plan, mode, obj_w,
                        force=(delta is True),
                    )
                    if d_res is not None:
                        out_d, g_d, coarse_d, displaced, stale, conv = d_res
                        out_d = _route_unseatable(
                            out_d, len(node_order), load, alive, cap
                        )
                        return (
                            out_d, g_d, coarse_d,
                            f"{mode}+delta", displaced, stale, conv,
                        )
            # Decide the actual code path up front so traces, profiler
            # labels, and SolveStats.mode all agree on what ran: one pure
            # rule (solve_route) over the directory's TOTAL padded rows.
            # Counting rows a device instead sent 4,194,304 rows on four
            # chips to the dense sharded branch, whose rows x nodes cost is
            # built whole on the host first.
            route = solve_route(
                mode, bucket, self._route_devices(), jax.default_backend(),
                obj_w is not None, self._mesh is not None,
            )
            route_hier = route.path == "hierarchical" and mode != "hierarchical"
            collapse = route.path == "collapsed"
            solved_as = route.solved_as
            with span("placement_solve", mode=solved_as, n=n):
                def _repair_exact(assignment_padded):
                    """Exact integer quotas at bucket shape (trace reuse);
                    movers evicted first so repair adds ~zero churn."""
                    from ..ops import exact_quota_repair

                    cap_alive = cap * alive
                    m_axis = cap_alive.shape[0]
                    real = jnp.arange(bucket) < n
                    idx_full = jnp.where(real, assignment_padded, m_axis)
                    expected = jnp.concatenate(
                        [
                            cap_alive
                            / jnp.maximum(jnp.sum(cap_alive), 1e-30)
                            * n,
                            jnp.asarray([bucket - n], jnp.float32),
                        ]
                    )
                    cur_full = jnp.zeros((bucket,), jnp.int32).at[:n].set(
                        jnp.asarray(cur_idx)
                    )
                    repaired = exact_quota_repair(
                        idx_full,
                        expected,
                        prefer_keep=jnp.where(real, idx_full == cur_full, True),
                        # A tied unit stays on the node that holds it NOW
                        # (the delta route's rule too), not where the
                        # plan's rounding noise put a row more.
                        tie_counts=jnp.bincount(
                            jnp.where(real, cur_full, m_axis), length=m_axis + 1
                        ),
                    )
                    return _guard_sentinel_spill(
                        repaired, real, m_axis, cap_alive
                    )

                coarse_g = None
                conv = {}
                if route.path == "hierarchical":
                    # Never materializes the flat (bucket x node_axis) cost.
                    assignment, g, coarse_g, conv = self._hierarchical_solve(
                        keys, node_order, cap, alive,
                        cur_idx=cur_idx if route_hier else None,
                        move_cost=self._move_cost if route_hier else 0.0,
                        move_w=obj_w if route_hier else None,
                        coarse_g_init=plan.coarse_g if plan is not None else None,
                        mesh=self._route_mesh(route),
                        whole=True,  # the snapshot: hashed identities are kept
                    )
                    # Mesh x chunk composed dispatch stamps its suffix so
                    # SolveStats.mode attributes the actual executable
                    # shape (the span above keeps the base mode label).
                    solved_as = solved_as + conv.pop("mode_suffix", "")
                elif collapse:
                    # CLASS-COLLAPSED exact solve (ops/structured.py): the
                    # flat cost model is a per-node vector plus a stay-put
                    # diagonal, so every object with the same current seat
                    # has an identical cost row and the (N x M) problem
                    # collapses EXACTLY to (M x M) — N drops out of the
                    # device solve entirely (<50 ms class at ANY N). The
                    # dense path below remains for mesh-sharded solves
                    # (per-shard capacity splits break the pure-class
                    # structure) and anything with per-object costs.
                    from ..ops.structured import class_quotas

                    base_cost = build_cost_matrix(
                        jnp.zeros_like(load), cap, alive
                    )[0]
                    counts = jnp.bincount(
                        jnp.asarray(cur_idx), length=base_cost.shape[0]
                    )
                    # The class problem is tiny (M x M), so sharpen eps
                    # until off-diagonal leakage is negligible: soft-plan
                    # off-diag mass scales like M * exp(-move_cost/eps),
                    # and at the default eps (0.05, ratio 10) that is ~5%
                    # of all objects moved for no reason. Ratio >= 25 puts
                    # the leak below 1e-8; the log-domain solver is stable
                    # at any eps.
                    class_eps = min(
                        self._eps, self._move_cost / 25.0 if self._move_cost > 0 else self._eps
                    )
                    quotas, g, cls_err = class_quotas(
                        base_cost,
                        counts,
                        cap * alive,
                        move_cost=self._move_cost,
                        eps=class_eps,
                        n_iters=self._n_iters,
                        # Warm-start even the FULL collapsed solve from the
                        # previous plan's potentials: converged-from-warm
                        # matches converged-from-cold within tolerance and
                        # shaves iterations when liveness barely moved.
                        g_init=(
                            jnp.asarray(plan.g)
                            if plan is not None and plan.g is not None
                            else None
                        ),
                    )
                    # Device expansion (exact parity with the host
                    # _apply_class_quotas, tested): the whole decision —
                    # counts -> class solve -> expansion -> exact repair —
                    # stays one device pipeline; the only host pull is the
                    # final int32 assignment below. Padding rows expand to
                    # garbage and are overridden by the repair's sentinel.
                    from ..ops.structured import expand_class_quotas

                    cur_padded = jnp.zeros((bucket,), jnp.int32).at[:n].set(
                        jnp.asarray(cur_idx)
                    )
                    expanded = expand_class_quotas(quotas, cur_padded)
                    # Column sums of per-row-rounded quotas are only
                    # approximately capacity; the shared repair makes node
                    # loads exactly integer-quota (still O(N log N)).
                    assignment = _repair_exact(expanded)
                    conv = {
                        "solver_iters": self._n_iters,
                        "residual": float(np.asarray(cls_err)),
                        "warm_ratio": _seed_warm_ratio(
                            plan.g if plan is not None else None
                        ),
                    }
                else:
                    base_cost = build_cost_matrix(jnp.zeros_like(load), cap, alive)
                    cost = jnp.broadcast_to(base_cost, (bucket, base_cost.shape[1]))
                    if self._move_cost > 0:
                        # Stay-put discount on each object's current seat: a
                        # re-solve must pay move_cost to relocate an object,
                        # so only capacity pressure (dead nodes, skew) moves
                        # anything. Discounts on dead seats are inert — the
                        # dead column is already priced at DEAD_NODE_COST.
                        # With per-object prices (obj_w) a hot/heavy actor's
                        # seat is discounted MORE, so when capacity forces
                        # some share to move the solver evicts cold objects
                        # first.
                        stay = (
                            self._move_cost
                            if obj_w is None
                            else self._move_cost * jnp.asarray(obj_w)
                        )
                        cost = cost.at[jnp.arange(n), jnp.asarray(cur_idx)].add(
                            -stay
                        )
                    mass = jnp.concatenate(
                        [jnp.ones((n,), jnp.float32), jnp.zeros((bucket - n,), jnp.float32)]
                    )
                    if mode in ("sinkhorn", "scaling"):
                        # Reachable with a mesh (shard-local capacity
                        # splits break the pure-class structure) or with
                        # per-object prices (obj_w makes cost rows
                        # distinct, so the class collapse is off and the
                        # dense single-chip solvers run).
                        if self._mesh is not None:
                            from ..parallel import (
                                shard_cost,
                                sharded_scaling_sinkhorn,
                                sharded_sinkhorn,
                            )

                            cost = shard_cost(self._mesh, cost)
                            sharded = (
                                sharded_scaling_sinkhorn
                                if mode == "scaling"
                                else sharded_sinkhorn
                            )
                            f, g = sharded(
                                self._mesh, cost, mass, cap * alive,
                                eps=self._eps, n_iters=self._n_iters,
                            )
                            # The sharded solvers return no residual (a
                            # collective just for telemetry isn't worth
                            # it) and take no warm seed: cold by design.
                            conv = {
                                "solver_iters": self._n_iters,
                                "warm_ratio": 0.0,
                            }
                        else:
                            dense = (
                                scaling_sinkhorn
                                if mode == "scaling"
                                else sinkhorn
                            )
                            f, g, _err = dense(
                                cost, mass, cap * alive,
                                eps=self._eps, n_iters=self._n_iters,
                                g_init=(
                                    jnp.asarray(plan.g)
                                    if plan is not None and plan.g is not None
                                    else None
                                ),
                            )
                            conv = {
                                "solver_iters": self._n_iters,
                                "residual": float(np.asarray(_err)),
                                "warm_ratio": _seed_warm_ratio(
                                    plan.g if plan is not None else None
                                ),
                            }
                        assignment = plan_rounded_assign(cost, f, g, self._eps)
                        # Exact-capacity repair (bucket-shaped for trace
                        # reuse; padding rows ride a sentinel column; see
                        # _repair_exact above).
                        assignment = _repair_exact(assignment)
                    else:
                        # Churn-aware greedy: waterfilling lays *all* mass
                        # out by cumulative position, so a naive full
                        # re-solve would reshuffle boundary objects that
                        # didn't need to move. Instead each object KEEPS its
                        # seat iff the seat is alive and the object is
                        # within its node's capacity-fair share (per-node
                        # rank < fair); everything else — dead seats and
                        # over-fair overflow — is waterfilled into the
                        # survivors' remaining headroom. Churn then moves
                        # exactly the displaced share, and pure load skew
                        # moves only the overflow, mirroring what the
                        # move-cost discount does for the OT modes.
                        from ..ops.assignment import rank_within_group

                        cur = jnp.zeros((bucket,), jnp.int32).at[:n].set(
                            jnp.asarray(cur_idx)
                        )
                        # Rank of each object among its node's objects.
                        # Stable sort keeps padding rows (mass 0, cur 0)
                        # after the real rows of node 0, so real ranks are
                        # unaffected.
                        order, _, rank_sorted = rank_within_group(cur)
                        rank = jnp.zeros((bucket,), jnp.int32).at[order].set(
                            rank_sorted
                        )
                        cap_alive = cap * alive
                        fair = (
                            jnp.sum(mass)
                            * cap_alive
                            / jnp.maximum(jnp.sum(cap_alive), 1e-30)
                        )
                        keep = (alive[cur] > 0) & (mass > 0) & (rank < fair[cur])
                        kept_load = jnp.zeros_like(cap).at[cur].add(
                            jnp.where(keep, mass, 0.0)
                        )
                        refill = greedy_balanced_assign(
                            cost,
                            jnp.where(keep, 0.0, mass),
                            cap_alive,
                            node_load=kept_load,
                        )
                        assignment = jnp.where(keep, cur, refill)
                        g = None
            # (A stage of its own: NumPy sorts over millions of movers keep
            # the interpreter lock some 20 ms at a time beside the loop.)
            with stage("solve.transit"):
                out = _route_unseatable(
                    np.asarray(assignment)[:n], len(node_order), load, alive, cap
                )
                if (
                    obj_w is None
                    and mode != "hierarchical"
                    and not (route_hier and self._move_cost <= 0.0)
                ):
                    # One price for every object and every destination: the
                    # plan's per-node loads are what the solve decided, which
                    # rows carry them is not. That holds for a flat mode
                    # routed through the two-level solve too: its hashed
                    # identities are a balancing proxy, nobody's preference
                    # (a move_cost of 0 says moves are free there, and the
                    # rows then go where the proxy sends them).
                    out = _cancel_transit(out, cur_idx)
            # Communication-graph refinement (full solves only: the delta
            # path returned above, and its warm potentials price pure
            # balance). Runs on the already-routed assignment so the
            # refine's stay-put baseline is a feasible seating.
            if self._affinity_weight > 0.0 and self._edge_graph:
                try:
                    refined = self._affinity_refine(
                        keys, out, node_order, cap, alive
                    )
                except Exception:  # noqa: BLE001 - refine must never kill a solve
                    log.exception("affinity refine failed; keeping base solve")
                    refined = None
                if refined is not None:
                    out = _route_unseatable(
                        refined, len(node_order), load, alive, cap
                    )
                    solved_as = f"{solved_as}+affinity"
            return out, g, coarse_g, solved_as, n, False, conv

        def _solve_staged() -> tuple:
            c0 = _compile_seconds()
            with stage("solve.device") as st:
                out, g, coarse_g, solved_as, displaced, stale, conv = _solve()
            # No solve ran without capacity: its record carries no split.
            conv = {} if conv is None else _conv_timing(conv, st.ms, c0)
            # What the commit needs of the O(N) output, taken here: it reads
            # the solve's output and the snapshot only, and the commit's
            # epoch check says whether that snapshot is still the directory.
            # (The node axis grows only with the epoch: ``_node_index``.)
            with stage("solve.diff") as st_diff:
                mover_pos, seat_counts, slices = _commit_diff(out, cur_idx, seats)
                planned = None
                if move_sink is not None:
                    # Grouped emission: the migration engine batches one
                    # burst per (source, target) pair, so hand it the plan
                    # already ordered by that pair — contiguous runs become
                    # whole MigrateBatch frames.
                    planned = [
                        (keys[p], node_order[s], node_order[t])
                        for p, s, t in zip(
                            mover_pos.tolist(),
                            cur_idx[mover_pos].tolist(),
                            out[mover_pos].tolist(),
                        )
                    ]
                    planned.sort(key=lambda m: (m[1], m[2]))
            diff = (mover_pos, seat_counts, planned, slices, st_diff.ms)
            return out, g, coarse_g, st.ms, solved_as, displaced, stale, conv, diff

        (
            assignment, g, coarse_g, solve_ms, solved_as, displaced, stale, conv,
            (mover_pos, seat_counts, planned, diff_slices, diff_ms),
        ) = await asyncio.to_thread(_solve_staged)

        async with self._lock:
            if self._epoch != snapshot_epoch:
                # Record the discarded ATTEMPT as its own stats event (the
                # next completed solve archives it into history like any
                # other) instead of mutating the prior completed solve's
                # record in place — that flag-flip misrepresented a
                # finished solve as discarded once history kept it.
                self.stats = SolveStats(
                    n_objects=n,
                    n_nodes=len(self._node_order),
                    solve_ms=solve_ms,
                    displaced=displaced,
                    epoch=self._epoch,
                    mode=solved_as,
                    discarded=True,
                    history=self._archived_history(),
                    **_conv_fields(conv),
                )
                return 0
            # Touch only the movers: non-movers are _set_placement no-ops
            # by definition (epoch unchanged => directory equals the
            # cur_idx snapshot). Which rows move and the plan's seat counts
            # came with the solve (``solve.diff``, the worker thread): what
            # is left under the lock is O(movers) and O(nodes).
            hist = self._archived_history()
            with stage("solve.apply") as st_apply:
                if planned is not None:
                    # Plan, don't apply: a row flips when the sink's handoff
                    # commits (or never, if it aborts — the lazy request
                    # path and the next churn solve cover it).
                    moved = len(planned)
                else:
                    moved = 0
                    for p in mover_pos.tolist():
                        if self._set_placement(keys[p], int(assignment[p])):
                            moved += 1
                self._diff_commits += 1
                self._diff_rows += n
                self._diff_slices += diff_slices
                self._diff_busy_ms += diff_ms
                if solved_as.endswith("+delta"):
                    self._delta_displaced += displaced
                    self._delta_moved += moved
                if conv.get("devices", 0) > 1:
                    self._mesh_solves += 1
                    self._mesh_devices = conv["devices"]
                    self._mesh_cells += conv["devices"] * max(1, conv.get("chunks", 1))
                self._feat_rows_reused += conv.get("feat_rows_reused", 0)
                self._feat_rows_hashed += conv.get("feat_rows_hashed", 0)
                if g is not None:
                    self._g = g
                    self._g_fp = self._sched_fp()
                self._recount_loads()
                self._epoch += 1
                if not solved_as.endswith("+no_capacity"):
                    # Commit the plan the NEXT churn event deltas against. A
                    # delta that produced no fresh potentials (greedy fill,
                    # hierarchical, empty displaced set) carries the previous
                    # seeds forward; a full solve resets the staleness counter.
                    delta_used = solved_as.endswith("+delta")
                    self._plan = PlanState(
                        g=(
                            g
                            if g is not None
                            else (plan.g if delta_used and plan is not None else None)
                        ),
                        coarse_g=(
                            coarse_g
                            if coarse_g is not None
                            else (
                                plan.coarse_g
                                if delta_used and plan is not None
                                else None
                            )
                        ),
                        seat_counts=seat_counts,
                        epoch=self._epoch,
                        liveness_fp=self._sched_fp(),
                        delta_solves=(
                            plan.delta_solves + 1
                            if delta_used and plan is not None
                            else 0
                        ),
                        stale=stale,
                    )
            self.stats = SolveStats(
                n_objects=n,
                n_nodes=len(self._node_order),
                solve_ms=solve_ms,
                apply_ms=st_apply.ms,
                moved=moved,
                displaced=displaced,
                epoch=self._epoch,
                mode=solved_as,
                discarded=False,
                history=hist,
                **_conv_fields(conv),
            )
        if planned:
            # Outside the lock on purpose: each handoff calls back into
            # update()/lookup(), which take it.
            await move_sink(planned)
        return moved
