"""chip_smoke.py — the served path on the chip, once, through the entry points.

What it drives (defaults; every size is an argument):

* one process that owns the chip: a ``JaxObjectPlacement(mode="auto")`` —
  which must resolve to ``sinkhorn`` because the backend is ``tpu`` — shared
  by 8 real ``Server`` instances on loopback TCP with ``placement_daemon=True``,
  wired as ``examples/tpu_placement.py`` wires them, every other option at
  its default (so their load monitors run, and the directory's capacities
  move with the load they measure);
* a directory of 1,024 nodes (the 8 live servers plus 1,016 directory-only
  members in the membership storage the daemons read) and 1,048,576 objects
  seated through ``assign_batch`` (four 262,144-row device chunks);
* a ``Client`` with the directory as its ``placement_resolver`` sending
  requests to objects the directory seats on live servers, each answer checked
  against the server the directory names;
* a churn event (30 directory nodes and one live server leave membership) that
  the ``PlacementDaemon`` turns into a delta re-solve with no solver call from
  here; then a forced full re-solve (``sinkhorn+collapsed``) and a forced
  ``mode="hierarchical"`` re-solve (chunked two-level route);
* after each committed solve, host arithmetic on the directory mirror: every
  object on a live, uncordoned node, per-node load within the quota the exact
  repair promises for the capacities the solve was given (``overflow == 0``);
* the fused Pallas scaling kernel compiled WITHOUT interpret mode at the shape
  its dispatch rule admits, compared with the XLA scaling core;
* with >= 4 devices, the mesh x chunk route on a second directory.

Any phase that fails fails the run. With no TPU visible the script exits
non-zero in seconds and prints nothing on stdout, unless the explicit
``--rehearse-on-cpu`` flag is given: that runs the same phases at whatever
(tiny) sizes the arguments say, Pallas in interpret mode, and stamps
``"rehearsal": true`` and ``"platform": "cpu"`` into every line it prints.

    python chip_smoke.py                                   # on the chip
    python chip_smoke.py --rehearse-on-cpu --objects 2048 --nodes 16 \\
        --servers 3 --requests 24 --churn-nodes 2 \\
        --kernel-rows 64 --kernel-cols 128 --mesh-objects 4096 --mesh-nodes 16

Stdout is JSON lines, one per phase, then a summary line, then the result
line ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import argparse
import asyncio
import contextlib
import json
import os
import sys
import threading
import time

_T0 = time.perf_counter()
_STAMP: dict = {}  # {"rehearsal": True, "platform": "cpu"} under the flag
_PHASES: list[dict] = []


def _emit(record: dict) -> None:
    print(json.dumps({**record, **_STAMP}), flush=True)


def _note(msg: str) -> None:
    print(f"# [{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


# Compile-side accounting per phase (jax.monitoring; registered in main()).
_WATCH = {"backend_compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}


@contextlib.contextmanager
def phase(name: str):
    """Time one phase and print its record; an exception fails the run."""
    rec: dict = {"phase": name}
    before = dict(_WATCH)
    t0 = time.perf_counter()
    _note(f"{name} ...")
    yield rec
    rec["seconds"] = round(time.perf_counter() - t0, 3)
    rec["backend_compile_s"] = round(
        _WATCH["backend_compile_s"] - before["backend_compile_s"], 3
    )
    rec["cache_hits"] = _WATCH["cache_hits"] - before["cache_hits"]
    rec["cache_misses"] = _WATCH["cache_misses"] - before["cache_misses"]
    _PHASES.append(rec)
    _emit(rec)


def _solve_record(stats) -> dict:
    """The SolveStats fields later PRs read, verbatim."""
    if not (0 <= stats.compile_ms <= stats.solve_ms) or stats.exec_ms < 0:
        raise AssertionError(f"solve {stats.mode} has no compile/exec split: {stats}")
    return {
        "mode": stats.mode,
        "solve_ms": round(stats.solve_ms, 3),
        "compile_ms": stats.compile_ms,
        "exec_ms": stats.exec_ms,
        "apply_ms": round(stats.apply_ms, 3),
        "moved": stats.moved,
        "displaced": stats.displaced,
        "chunks": stats.chunks,
        "chunk_ms": stats.chunk_ms,
        "devices": stats.devices,
        "residual": stats.residual,
        "warm_ratio": stats.warm_ratio,
    }


def _parse_args() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--objects", type=int, default=1_048_576)
    ap.add_argument("--nodes", type=int, default=1_024)
    ap.add_argument("--servers", type=int, default=8)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--churn-nodes", type=int, default=30)
    ap.add_argument("--kernel-rows", type=int, default=262_144)
    ap.add_argument("--kernel-cols", type=int, default=1_024)
    ap.add_argument("--mesh-objects", type=int, default=4_194_304)
    ap.add_argument("--mesh-nodes", type=int, default=1_024)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearse-on-cpu",
        action="store_true",
        help="run on the CPU backend (never the default); output is stamped "
        '"rehearsal": true, "platform": "cpu"',
    )
    return ap.parse_args()


# ---------------------------------------------------------------------------
# Host arithmetic on the directory mirror
# ---------------------------------------------------------------------------


async def _seats(placement, ids, index_of) -> "np.ndarray":
    """Node index of every object, read back through ``lookup_batch``.

    Off the event loop: the live servers share this loop, and a second of
    bulk bookkeeping on it reads as load on every one of them (the
    directory then derates their capacity). ``lookup_batch`` never
    suspends, so a private loop in the worker thread can drive it."""
    import numpy as np

    def read() -> "np.ndarray":
        addrs = asyncio.run(placement.lookup_batch(ids))
        missing = sum(a is None for a in addrs)
        if missing:
            raise AssertionError(f"{missing} of {len(ids)} objects have no seat")
        return np.fromiter((index_of[a] for a in addrs), np.int64, count=len(addrs))

    return await asyncio.to_thread(read)


def _capacities(placement, node_order) -> "np.ndarray":
    """Per node index, the capacity the directory would give a solve right
    now: the node's declared capacity times its measured-load derate."""
    import numpy as np

    slots = placement._nodes
    return np.array(
        [slots[a].capacity * slots[a].reported_derate for a in node_order], np.float64
    )


def _check_directory(seats, cap, slack: int | None = 0, exact: bool = True) -> dict:
    """Every object on a schedulable node, per-node load within quota.

    ``cap`` is the capacity vector the solve was given, 0 for a node that
    was not schedulable. Node ``j``'s fair share is ``n * cap[j] /
    sum(cap)``. A flat solve's exact repair is one largest-remainder
    rounding of those shares, so every node lands on the floor or the
    ceiling of its own (``slack=0``). The two-level solve rounds twice in
    every (device, chunk) cell — groups to their share of the cell, nodes
    to their share of the group — so a node is within 2 of its fair share
    per cell: ``slack = 2 * cells - 1`` around floor and ceiling.
    ``slack=None`` checks liveness only (``assign_batch``'s waterfill
    promises no exact quota). ``exact=False`` is for the greedy mode
    (what ``mode="auto"`` resolves to off the chip): it moves no object a
    quota does not force out, so it promises the ceiling and no floor,
    and the underflow is reported, not held against it.
    """
    import numpy as np

    n = int(seats.shape[0])
    m = int(cap.shape[0])
    sched = cap > 0
    counts = np.bincount(seats, minlength=m)
    out = {
        "objects": n,
        "schedulable_nodes": int(sched.sum()),
        "on_unschedulable": int(counts[~sched].sum()),
        "max_load": int(counts[sched].max()),
        "min_load": int(counts[sched].min()),
    }
    if slack is not None:
        fair = n * cap[sched] / cap[sched].sum()
        # Unequal capacities: the solver's float32 shares may round one
        # that is an integer but for float error either way.
        eps = 0.0 if np.ptp(cap[sched]) == 0 else 1e-3
        live = counts[sched]
        out.update(
            overflow=int(np.maximum(live - (np.ceil(fair + eps) + slack), 0).sum()),
            underflow=int(np.maximum((np.floor(fair - eps) - slack) - live, 0).sum()),
            fair_load_min=round(float(fair.min()), 2),
            fair_load_max=round(float(fair.max()), 2),
            derated_nodes=int((cap[sched] < cap[sched].max()).sum()),
            slack=slack,
        )
    if out["on_unschedulable"] or out.get("overflow") or (exact and out.get("underflow")):
        raise AssertionError(f"directory check failed: {out}")
    return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


async def _run(args, summary: dict) -> None:
    import jax
    import numpy as np

    from rio_tpu import (
        AppData,
        Client,
        LocalStorage,
        ObjectId,
        Registry,
        Server,
        ServerInfo,
        ServiceObject,
        handler,
        message,
        native,
    )
    from rio_tpu.cluster.membership_protocol import LocalClusterProvider
    from rio_tpu.cluster.storage import Member
    from rio_tpu.commands import AdminCommand
    from rio_tpu.object_placement import jax_placement as jp
    from rio_tpu.registry.identifiable import type_id

    rehearsal = bool(_STAMP)
    rng = np.random.default_rng(args.seed)

    @message
    class Beat:
        n: int = 1

    @message
    class BeatAck:
        n: int = 0
        server: str = ""

    class Presence(ServiceObject):
        def __init__(self):
            self.beats = 0

        @handler
        async def beat(self, msg: Beat, ctx: AppData) -> BeatAck:
            self.beats += msg.n
            return BeatAck(n=self.beats, server=ctx.get(ServerInfo).address)

    tname = type_id(Presence)

    # -- cluster: membership rows, one directory, N live servers -------------
    with phase("cluster") as rec:
        members = LocalStorage()
        n_dir = args.nodes - args.servers
        if n_dir < args.churn_nodes or args.servers < 2:
            raise SystemExit("need nodes - servers >= churn-nodes and servers >= 2")
        # Directory-only members are rows in the storage the daemons read:
        # sync_members marks every node absent from the list dead. Their
        # addresses are loopback ones nobody listens on, so that a dial
        # (a daemon's handoff towards one) is refused at once anywhere.
        dir_nodes = [f"127.77.{i // 250}.{i % 250 + 1}:7000" for i in range(n_dir)]
        for addr in dir_nodes:
            await members.push(Member.from_address(addr, active=True))
        placement = jp.JaxObjectPlacement(mode="auto")
        servers = []
        for _ in range(args.servers):
            s = Server(
                address="127.0.0.1:0",
                registry=Registry().add_type(Presence),
                cluster_provider=LocalClusterProvider(members),
                object_placement_provider=placement,
                placement_daemon=True,
            )
            await s.prepare()
            await s.bind()
            servers.append(s)
        tasks = [asyncio.create_task(s.run()) for s in servers]
        live = [s.local_address for s in servers]
        for _ in range(200):
            if {m.address for m in await members.active_members()} >= set(live):
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("servers never registered in membership")
        placement.sync_members(await members.members())
        # Each daemon treats the servers that registered after its first
        # poll as churn and re-solves the (still empty) directory; let
        # that pass before anything is seated.
        for _ in range(1200):
            if all(
                s.placement_daemon is not None and s.placement_daemon.stats.polls >= 3
                for s in servers
            ):
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("the placement daemons never started polling")
        node_order = list(placement._node_order)  # index -> address
        index_of = {a: i for i, a in enumerate(node_order)}
        if len(node_order) != args.nodes:
            raise AssertionError(f"{len(node_order)} directory nodes, want {args.nodes}")
        rec.update(
            nodes=len(node_order),
            live_servers=len(live),
            directory_only=n_dir,
            native_codec=native.status(),
        )

    async def capacities_now() -> "np.ndarray":
        """The capacity vector a solve dispatched now would be given: the
        directory's own capacity x derate, 0 where the membership rows
        say inactive."""
        active = {m.address for m in await members.active_members()}
        return _capacities(placement, node_order) * np.array(
            [a in active for a in node_order], np.float64
        )

    async def forced_solve(label: str, slack: int, **kw) -> dict:
        """One committed ``rebalance(**kw)``, then the directory check
        against the capacities read at the call (the solve snapshots
        them before it first suspends)."""
        discarded = []
        for _ in range(5):
            cap = await capacities_now()
            await placement.rebalance(**kw)
            stats = placement.stats
            if not stats.discarded:
                break
            # The directory moved under the solve: the provider threw the
            # result away. Nothing here should cause that; it stays in
            # the output when something did.
            discarded.append(_solve_record(stats))
            _note(f"{label}: attempt {len(discarded)} lost an epoch race, retrying")
        else:
            raise AssertionError(f"{label}: 5 solves in a row were discarded")
        seats = await _seats(placement, ids, index_of)
        out = _solve_record(stats)
        out["discarded_attempts"] = discarded
        out["directory"] = _check_directory(
            seats, cap, slack, exact=not stats.mode.startswith("greedy")
        )
        return out

    client = Client(
        members,
        placement_resolver=lambda t, i: placement.lookup(ObjectId(t, i)),
    )

    async def drive(label: str, picks: list[int], seats) -> dict:
        """Send one request per picked object; the answer must come from
        the server the directory names."""
        answered = failed = misrouted = 0
        first_error = None
        for i in picks:
            want = node_order[int(seats[i])]
            try:
                out = await client.send(Presence, ids[i].id, Beat(), returns=BeatAck)
            except Exception as e:  # noqa: BLE001 - counted; any failure fails the phase
                failed += 1
                first_error = first_error or repr(e)
                continue
            answered += 1
            if out.server != want:
                misrouted += 1
                first_error = first_error or f"{ids[i]}: {out.server} != {want}"
        res = {
            "sent": len(picks),
            "answered": answered,
            "failed": failed,
            "misrouted": misrouted,
            "redirects": client.stats.redirects,
        }
        if failed or misrouted or not picks:
            raise AssertionError(f"{label}: {res} first_error={first_error}")
        return res

    def pick_on(seats, addresses: list[str], among=None, limit=None) -> list[int]:
        """Up to ``limit`` object indices seated on ``addresses``."""
        limit = args.requests if limit is None else limit
        want = np.isin(seats, [index_of[a] for a in addresses])
        if among is not None:
            want &= among
        pool = np.nonzero(want)[0]
        if pool.shape[0] > limit:
            pool = rng.choice(pool, size=limit, replace=False)
        return sorted(int(i) for i in pool)

    try:
        # -- seat: the whole directory through assign_batch ------------------
        with phase("seat") as rec:
            ids = await asyncio.to_thread(
                lambda: [ObjectId(tname, str(i)) for i in range(args.objects)]
            )
            t0 = time.perf_counter()
            addrs = await placement.assign_batch(ids)
            rec["assign_batch_s"] = round(time.perf_counter() - t0, 3)
            if len(addrs) != args.objects or placement.count() != args.objects:
                raise AssertionError("assign_batch did not seat every object")
            chunks = -(-args.objects // placement._MAX_PLACE_CHUNK)
            seats = await _seats(placement, ids, index_of)
            rec.update(
                objects=args.objects,
                device_chunks=chunks,
                chunk_rows=min(args.objects, placement._MAX_PLACE_CHUNK),
                directory=_check_directory(seats, await capacities_now(), None),
            )

        with phase("requests_before_churn") as rec:
            rec.update(await drive("before churn", pick_on(seats, live), seats))

        # -- plan: the first full solve commits the plan deltas run against --
        with phase("solve_full_first") as rec:
            rec.update(await forced_solve("first full solve", 0, delta=False))
            mode = placement._solver_mode()
            want_mode = "greedy" if rehearsal else "sinkhorn"
            if mode != want_mode or rec["mode"] != (
                "greedy" if rehearsal else "sinkhorn+collapsed"
            ):
                raise AssertionError(
                    f'mode="auto" resolved to {mode!r} and solved as '
                    f'{rec["mode"]!r} on backend {jax.default_backend()!r}'
                )
            rec["auto_resolved_to"] = mode

        # -- churn: the daemons re-solve, this script calls no solver --------
        with phase("churn_daemon_delta") as rec:
            before = await _seats(placement, ids, index_of)
            victim = servers[-1]
            gone = [
                dir_nodes[int(i)]
                for i in rng.choice(n_dir, size=args.churn_nodes, replace=False)
            ] + [victim.local_address]
            gone_idx = np.array([index_of[a] for a in gone])
            displaced = np.isin(before, gone_idx)
            # The delta route moves the displaced and nobody else only
            # while no survivor is over its quota, and a server derated by
            # this script's own bookkeeping would be. The event waits
            # until the servers measure themselves idle again.
            t0 = time.perf_counter()
            full = np.array([placement._nodes[a].capacity for a in node_order])
            quiet = 0
            for _ in range(1200):
                quiet = quiet + 1 if (_capacities(placement, node_order) == full).all() else 0
                if quiet >= 20:
                    break
                await asyncio.sleep(0.1)
            else:
                raise AssertionError("the servers never measured themselves idle")
            rec["idle_wait_s"] = round(time.perf_counter() - t0, 3)
            cap_event = await capacities_now()
            cap_event[gone_idx] = 0.0
            epoch_start = placement.stats.epoch

            def daemon_solves() -> list:
                """Committed solves since the churn began (none is ours)."""
                st = placement.stats
                return [
                    s for s in [*st.history, st]
                    if s.epoch > epoch_start and not s.discarded
                ]

            t0 = time.perf_counter()
            # A live server leaves by exiting (its own provider would push
            # its row active again otherwise); Server.run deregisters it.
            # The directory rows flip right behind it, inside the daemons'
            # debounce window: one churn event, not two.
            victim.admin_sender().queue.put_nowait(AdminCommand.server_exit())
            await asyncio.wait_for(asyncio.shield(tasks[-1]), 30.0)
            for addr in gone[:-1]:
                ip, _, port = addr.rpartition(":")
                await members.set_inactive(ip, int(port))
            after = before
            for _ in range(1200):
                await asyncio.sleep(0.25)
                if not daemon_solves():
                    continue
                after = await _seats(placement, ids, index_of)
                if not np.isin(after, gone_idx).any():
                    break
            else:
                raise AssertionError(
                    "the placement daemons never re-seated the departed "
                    f"nodes' objects: stats={placement.stats}"
                )
            rec["reseat_s"] = round(time.perf_counter() - t0, 3)
            committed = daemon_solves()
            dstats = [s.placement_daemon.stats for s in servers]
            rec.update(
                departed_nodes=len(gone),
                displaced=int(displaced.sum()),
                moved=int((after != before).sum()),
                undisplaced_moved=int(((after != before) & ~displaced).sum()),
                daemon_rebalances=sum(d.rebalances for d in dstats),
                daemon_delta_rebalances=sum(d.delta_rebalances for d in dstats),
                daemon_discarded=sum(d.rebalances_discarded for d in dstats),
                daemon_skipped=sum(d.rebalances_skipped for d in dstats),
                daemon_errors=sum(d.errors for d in dstats),
                daemon_solves=[_solve_record(s) for s in committed],
            )
            if (
                not all(s.mode.endswith("+delta") for s in committed)
                or rec["daemon_delta_rebalances"] < 1
                or rec["daemon_errors"]
                or rec["undisplaced_moved"]
                or rec["moved"] != rec["displaced"]
            ):
                raise AssertionError(f"churn was not served by a daemon delta: {rec}")
            live_now = [a for a in live if a != victim.local_address]
            rec["directory"] = _check_directory(
                after, cap_event, 0,
                exact=not any(s.mode.startswith("greedy") for s in committed),
            )

        with phase("requests_after_churn") as rec:
            # Objects the device solve moved from a departed node onto a
            # live server: that server must now answer for them.
            moved_in = pick_on(after, live_now, among=displaced)
            if not moved_in:
                raise AssertionError("the delta solve moved nothing onto a live server")
            rec["moved_onto_live"] = await drive("moved onto live", moved_in, after)
            rec["stayed_on_live"] = await drive(
                "stayed on live", pick_on(after, live_now, among=~displaced), after
            )

        # -- forced full solves: collapsed, then the chunked two-level route --
        with phase("solve_full_collapsed") as rec:
            # On the chip the mode is the directory's own ("auto" resolved
            # to sinkhorn); the CPU rehearsal, where "auto" is greedy,
            # names it so that the collapsed pipeline is rehearsed too.
            kw = {"mode": "sinkhorn"} if rehearsal else {}
            rec.update(await forced_solve("forced full solve", 0, delta=False, **kw))
            if rec["mode"] != "sinkhorn+collapsed":
                raise AssertionError(f"full solve ran as {rec['mode']}")

        with phase("solve_hierarchical") as rec:
            bucket = jp._next_bucket(args.objects)
            want_chunks = max(1, bucket // jp._HIER_CHUNK_ROWS)
            rec.update(
                await forced_solve(
                    "hierarchical solve", 2 * want_chunks - 1,
                    mode="hierarchical", delta=False,
                )
            )
            if rec["mode"] != "hierarchical" or max(1, rec["chunks"]) != want_chunks:
                raise AssertionError(
                    f"hierarchical solve ran as {rec['mode']} in "
                    f"{rec['chunks']} chunks, want {want_chunks}"
                )
            if want_chunks > 1 and len(rec["chunk_ms"]) != want_chunks:
                raise AssertionError(f"no per-chunk timings: {rec['chunk_ms']}")

        with phase("requests_after_resolves") as rec:
            final = await _seats(placement, ids, index_of)
            rec.update(
                await drive(
                    "after hierarchical",
                    pick_on(final, live_now, among=final != after),
                    final,
                )
            )
    finally:
        client.close()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    summary["auto_resolved_to"] = mode
    summary["native_codec"] = native.status()


def _kernel_phase(args) -> None:
    """Compile the fused Pallas scaling kernel at the shape its dispatch
    rule admits and compare it with the XLA scaling core on the same
    inputs (tolerance of tests/test_scaling_sinkhorn.py: 1e-3 on the
    potentials)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rio_tpu.ops import scaling

    rehearsal = bool(_STAMP)
    n, m = args.kernel_rows, args.kernel_cols
    block = 1024 if n % 1024 == 0 else 8
    eps, iters = 0.05, 10
    with phase("kernel_fused_scaling") as rec:
        k1, k2 = jax.random.split(jax.random.PRNGKey(args.seed))
        cost = jax.random.uniform(k1, (n, m), jnp.float32)
        mass = jnp.ones((n,), jnp.float32)
        cap = jax.random.uniform(k2, (m,), jnp.float32) + 0.5
        rec.update(
            rows=n, cols=m, block_rows=block, kernel_dtype="bfloat16",
            interpret=rehearsal,
            dispatch_rule_selects=scaling.scaling_impl_for(n, m, block_rows=block),
        )
        if not rehearsal and rec["dispatch_rule_selects"] != "pallas_fused":
            raise AssertionError(
                f"scaling_impl_for({n}, {m}) = {rec['dispatch_rule_selects']}: "
                "the kernel phase must run at a shape the rule admits"
            )
        t0 = time.perf_counter()
        u_p, v_p, K_p, _ = scaling.pallas_scaling_core(
            cost, mass, cap, eps=eps, n_iters=iters, block_rows=block,
            interpret=rehearsal,
        )
        jax.block_until_ready((u_p, v_p))
        rec["pallas_first_call_s"] = round(time.perf_counter() - t0, 3)
        u_x, v_x, K_x, _ = scaling.scaling_core(
            cost, mass, cap, eps=eps, n_iters=iters
        )
        jax.block_until_ready((u_x, v_x))
        f_p, g_p = eps * jnp.log(u_p), eps * jnp.log(v_p)
        f_x, g_x = eps * jnp.log(u_x), eps * jnp.log(v_x)
        rec["max_abs_diff_f"] = float(jnp.max(jnp.abs(f_p - f_x)))
        rec["max_abs_diff_g"] = float(jnp.max(jnp.abs(g_p - g_x)))
        np.testing.assert_allclose(np.asarray(f_p), np.asarray(f_x), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_x), rtol=1e-3, atol=1e-3)
        if not bool(jnp.all(jnp.isfinite(f_p)) & jnp.all(jnp.isfinite(g_p))):
            raise AssertionError("non-finite potentials from the fused kernel")
        rec["verdict"] = "compiled and within tolerance"


def _bytes_in_use(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices]


class _MemorySampler:
    """Highest ``bytes_in_use`` per device seen while the block runs,
    polled from a thread every 10 ms (the solve runs off the event loop
    and releases the GIL while the devices work)."""

    def __init__(self, devices) -> None:
        self.devices = devices
        self.peak = _bytes_in_use(devices)
        self.samples = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self) -> None:
        while not self._stop.wait(0.01):
            self.peak = [max(a, b) for a, b in zip(self.peak, _bytes_in_use(self.devices))]
            self.samples += 1

    def __enter__(self) -> "_MemorySampler":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


async def _mesh_phase(args) -> None:
    """The mesh x chunk route over every visible device, on a second
    directory built as ``JaxObjectPlacement(mode="hierarchical", mesh=...)``."""
    import jax
    import numpy as np

    from rio_tpu import ObjectId
    from rio_tpu.object_placement import jax_placement as jp
    from rio_tpu.parallel import make_mesh, sharded_hierarchical_assign

    devices = jax.devices()
    if len(devices) < 4:
        _emit({"mesh_phase": f"not run ({len(devices)} device)"})
        return
    with phase("mesh_chunk_solve") as rec:
        mesh = make_mesh()
        p = jp.JaxObjectPlacement(mode="hierarchical", mesh=mesh)
        nodes = [f"127.78.{i // 250}.{i % 250 + 1}:7000" for i in range(args.mesh_nodes)]
        p.sync_members(nodes)
        ids = [ObjectId("MeshSmoke", str(i)) for i in range(args.mesh_objects)]
        await p.assign_batch(ids)
        # Where the solve's memory goes, device by device. The lifetime
        # peak cannot say: device 0 also ran every earlier phase and the
        # seating above. So the bytes in use are sampled while the solve
        # runs, against each device's level just before it.
        base = _bytes_in_use(devices)
        with _MemorySampler(devices) as sampler:
            await p.rebalance(delta=False)
        st = p.stats
        rec.update(_solve_record(st), objects=args.mesh_objects, nodes=args.mesh_nodes)
        per_dev = -(-jp._next_bucket(args.mesh_objects) // len(devices))
        want_chunks = max(1, per_dev // jp._HIER_CHUNK_ROWS)
        want_mode = "hierarchical+mesh_chunk" if want_chunks > 1 else "hierarchical"
        if (
            st.discarded
            or st.mode != want_mode
            or st.devices != len(devices)
            or max(1, st.chunks) != want_chunks
        ):
            raise AssertionError(
                f"mesh solve ran as {st.mode} on {st.devices} devices in "
                f"{st.chunks} chunks; want {want_mode}, {len(devices)}, {want_chunks}"
            )
        index_of = {a: i for i, a in enumerate(p._node_order)}
        seats = await _seats(p, ids, index_of)
        rec["directory"] = _check_directory(
            seats, _capacities(p, p._node_order), 2 * want_chunks * len(devices) - 1
        )
        grew = [hi - lo for hi, lo in zip(sampler.peak, base)]
        rec.update(
            solve_bytes_per_device=grew,
            memory_samples=sampler.samples,
            peak_bytes_per_device=[
                int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
                for d in devices
            ],
        )
        # Every device holds its own rows and no more: the feature block
        # goes shard by shard from the host to its device. Committed to
        # device 0 first, it would sit there whole (objects x 16 float32,
        # 268 MB at the default size) for the length of the solve.
        if devices[0].platform != "cpu" and not (
            min(grew) >= (1 << 20) and grew[0] <= 1.5 * max(grew[1:])
        ):
            raise AssertionError(
                f"the solve's memory is not spread evenly over the devices: {rec}"
            )
    with phase("mesh_result_shards") as rec:
        # Where a sharded solve's result lives: one shard per device.
        rows, d, m = 4096 * len(devices), 16, args.mesh_nodes
        g = max(1, m // 8)
        rng = np.random.default_rng(args.seed)
        res = sharded_hierarchical_assign(
            mesh,
            rng.standard_normal((rows, d), np.float32),
            rng.standard_normal((d, m), np.float32) * 0.2,
            np.ones((m,), np.float32), np.ones((m,), np.float32),
            n_groups=g,
        )
        jax.block_until_ready(res.assignment)
        shard_devices = sorted(str(s.device) for s in res.assignment.addressable_shards)
        rec.update(
            rows=rows, overflow=int(res.overflow),
            shard_devices=shard_devices,
            shard_rows=[int(s.data.shape[0]) for s in res.assignment.addressable_shards],
        )
        if len(set(shard_devices)) != len(devices) or rec["overflow"]:
            raise AssertionError(f"result is not spread over every device: {rec}")


def main() -> int:
    args = _parse_args()
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        _STAMP.update(rehearsal=True, platform="cpu")
    import jax

    backend = jax.default_backend()
    if not args.rehearse_on_cpu and backend != "tpu":
        print(
            f"chip_smoke: jax.default_backend() is {backend!r}, not 'tpu' — no "
            "accelerator, nothing measured (--rehearse-on-cpu rehearses the "
            "phases at a tiny size)",
            file=sys.stderr,
        )
        return 2

    import importlib.metadata as md

    import jaxlib

    from rio_tpu.utils.jaxenv import compile_cache_dir

    def _on_duration(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _WATCH["backend_compile_s"] += duration

    def _on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _WATCH["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _WATCH["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    cache_dir = compile_cache_dir()

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "absent"
    env = {
        "phase": "env",
        "device": device,
        "host_cpu_count": os.cpu_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "python": sys.version.split()[0],
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": cache_entries(),
        "sizes": {k: v for k, v in vars(args).items() if k != "rehearse_on_cpu"},
    }
    _emit(env)

    summary: dict = {}
    asyncio.run(_run(args, summary))
    _kernel_phase(args)
    asyncio.run(_mesh_phase(args))

    mem = devices[0].memory_stats() or {}
    summary.update(
        phase="summary",
        seconds_total=round(time.perf_counter() - _T0, 3),
        seconds_per_phase={p["phase"]: p["seconds"] for p in _PHASES},
        backend_compile_s_total=round(_WATCH["backend_compile_s"], 3),
        cache_hits=_WATCH["cache_hits"],
        cache_misses=_WATCH["cache_misses"],
        solve_modes=[
            p["mode"] for p in _PHASES if "mode" in p
        ] + [s["mode"] for p in _PHASES for s in p.get("daemon_solves", ())],
        peak_device_bytes=int(mem.get("peak_bytes_in_use", -1)),
        compile_cache_dir=cache_dir,
        compile_cache_entries_at_exit=cache_entries(),
    )
    _emit(summary)
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
