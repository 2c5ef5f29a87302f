"""Member churn, played: every ``period_s`` seconds ``leave`` active members
that are not this host's live servers close their listener and turn
inactive, and those that left ``rejoin_after`` events earlier bind again and
turn active, in ONE flip of the membership table.

``harness.build_cluster`` wrote the members as rows with loopback addresses
nobody listens on. ``warm()`` stands a ``Server`` of the program behind
every active row (``placement_daemon=False``, ``load_monitor=False``: they
stand for other hosts, the configuration's ``reduced`` says why), sharing
the directory object as every server of this system must, then plays one
whole event outside the window and waits until it is served, so that the
delta route, the class refresh and the hand-off's connections are compiled
and open before the window. The first ``rejoin_after`` events' rejoiners are
the members that were down when the run started, in equal groups.

Per event the generator records the flip's time and, from a worker thread
that reads the directory's per-node row counts every ``poll_s`` seconds, the
time it was SERVED: no row on one of the event's leavers, and every one of
its rejoiners holding at least the floor of an even share. Event times are
jittered by ``jitter_s`` against the daemons' poll, from ``--seed``.

Every wait here is bounded (``serve_limit_s`` after an event's flip): on a
program that cannot serve the churn the run still ends, `correct` false.
"""

import asyncio
import os
import threading
import time

import numpy as np

from benchmark.harness import note, run_sync

KEY = "_churn"
#: A wake of the watcher's thread this many seconds late is a stop of the process.
STOP_S = 2.0


def open_descriptors() -> int:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return -1


class Members:
    """The other members: a ``Server`` of the program behind each active row."""

    def __init__(self, run) -> None:
        c = run.cluster
        self.run = run
        self.live = set(c.live)
        self.idx = [i for i, a in enumerate(c.node_order) if a not in self.live]
        self.servers: dict[int, tuple] = {}  # node index -> (Server, task)

    def _server(self, i: int):
        from rio_tpu import Server
        from rio_tpu.cluster.membership_protocol import LocalClusterProvider

        c = self.run.cluster
        return Server(
            address=c.node_order[i],
            registry=self.run.app.registry(),
            cluster_provider=LocalClusterProvider(c.members),
            object_placement_provider=c.placement,
            placement_daemon=False,
            load_monitor=False,
        )

    async def bind(self, i: int):
        s = self._server(i)
        await s.prepare()
        await s.bind()
        return s

    def start(self, i: int, s) -> None:
        task = asyncio.create_task(s.run())
        self.servers[i] = (s, task)
        self.run.cluster.tasks.append(task)  # cancelled with the cluster

    async def stop(self, indices) -> None:
        gone = [self.servers.pop(i) for i in indices if i in self.servers]
        for _, task in gone:
            task.cancel()
        await asyncio.gather(*(t for _, t in gone), return_exceptions=True)

    async def flip(self, leavers, rejoiners) -> float:
        """One event. The rejoiners' listeners are bound first (their rows
        are still inactive: nobody dials them); then, with no turn of the loop
        between, every row flips; then the leavers' servers are told to stop
        (each closes its listener and its connections as it goes)."""
        c = self.run.cluster
        bound = [(i, await self.bind(i)) for i in rejoiners]
        t_flip = time.perf_counter()
        for i in leavers:
            host, _, port = c.node_order[i].rpartition(":")
            run_sync(c.members.set_inactive(host, int(port)))
        for i in rejoiners:
            host, _, port = c.node_order[i].rpartition(":")
            run_sync(c.members.set_active(host, int(port)))
        for i, s in bound:
            self.start(i, s)
        await self.stop(leavers)
        return t_flip


class Watcher(threading.Thread):
    """Reads the directory's per-node row counts off the loop and stamps
    each pending event when it is served."""

    def __init__(self, placement, n_nodes: int, poll_s: float) -> None:
        super().__init__(name="bench-churn-watcher", daemon=True)
        self.placement = placement
        self.n_nodes = n_nodes
        self.poll_s = poll_s
        self.pending: list = []  # events not served yet (appended by the loop)
        self.stops: list = []  # (from, to): the PROCESS did not run (see run)
        self.stop_event = threading.Event()

    def counts(self) -> np.ndarray:
        # The mirror's per-node index, read for its sizes only: one len() a
        # node, no key touched.
        by_node = self.placement._by_node
        return np.fromiter(
            (len(by_node.get(j, ())) for j in range(self.n_nodes)), np.int64, self.n_nodes
        )

    def run(self) -> None:
        # This thread asks for ``poll_s`` of sleep and no more, and nothing of
        # the program holds the interpreter lock for seconds (a full
        # collection is its longest hold, ~0.15 s): a wake STOP_S late means
        # the machine did not run the process at all (PERF.md Findings PR 23
        # and PR 25: stops of 2 to 10 s, a few in a hundred runs). The audit
        # takes that time out of an event's serve time; a loop that is merely
        # saturated does not keep this thread from waking.
        woke = time.perf_counter()
        while not self.stop_event.wait(self.poll_s):
            now = time.perf_counter()
            if now - woke - self.poll_s > STOP_S:
                self.stops.append((woke, now))
            woke = now
            if not self.pending:
                continue
            counts = self.counts()
            now = woke = time.perf_counter()
            for ev in list(self.pending):
                if (
                    counts[ev["leavers"]].sum() == 0
                    and (counts[ev["rejoiners"]] >= ev["share_floor"]).all()
                ):
                    ev["t_served"] = now
                    self.pending.remove(ev)


def _state(run, params) -> dict:
    st = run.log.get(KEY)
    if st is None:
        c = run.cluster
        members = Members(run)
        active = run_sync(c.active_mask())
        down = [i for i in members.idx if not active[i]]
        k = params["rejoin_after"]
        if len(down) != k * params["leave"]:
            raise RuntimeError(
                f"{len(down)} members down at start; the mix wants "
                f"{k} groups of {params['leave']}"
            )
        rng = run.rng(params["name"])
        down = rng.permutation(down).tolist()
        st = run.log[KEY] = {
            "members": members,
            "rng": rng,
            "active": active,  # the generator's own book of who is active
            "groups": [down[g::k] for g in range(k)],  # rejoiners of the next k events
            "events": [],
            "watcher": Watcher(c.placement, len(c.node_order), params["poll_s"]),
        }
    return st


async def _event(run, params, st: dict, in_window: bool) -> dict:
    members, active = st["members"], st["active"]
    can_leave = np.array([i for i in members.idx if active[i]])
    leavers = np.sort(st["rng"].choice(can_leave, params["leave"], replace=False))
    rejoiners = np.array(sorted(st["groups"].pop(0)), np.int64)
    st["groups"].append(leavers.tolist())
    before = st["watcher"].counts()
    active[leavers] = False
    active[rejoiners] = True
    ev = {
        "in_window": in_window, "leavers": leavers, "rejoiners": rejoiners,
        "before": before, "active": active.copy(),
        "share_floor": int(before.sum()) // int(active.sum()),
        "t_flip": 0.0, "t_served": float("nan"),
        "descriptors": open_descriptors(),
    }
    with run.span("bench.churn.flip"):
        ev["t_flip"] = await members.flip(leavers.tolist(), rejoiners.tolist())
    st["events"].append(ev)
    st["watcher"].pending.append(ev)
    return ev


async def _await_served(ev: dict, limit_s: float) -> bool:
    while ev["t_served"] != ev["t_served"]:  # NaN: not served yet
        if time.perf_counter() - ev["t_flip"] > limit_s:
            return False
        await asyncio.sleep(0.02)
    return True


async def _derates_at_rest(run, quiet_s: float = 4.0, limit_s: float = 30.0) -> None:
    """Wait (bounded) until the directory has applied no derate step for
    ``quiet_s`` seconds: two refreshes of every monitor's view. Set-up's own
    multi-second holds of the loop (seating, the full solve, a thousand
    binds) are load that lasted, and the monitors price it; the window is to
    open on the deployment's steady state, not on set-up's wake. A program
    without the counter is not waited for."""
    gauges = getattr(run.cluster.placement, "place_gauges", None)
    key = "rio.load.derate_steps"
    if gauges is None or key not in gauges():
        return
    t0 = last_change = time.perf_counter()
    seen = gauges()[key]
    while time.perf_counter() - last_change < quiet_s and time.perf_counter() - t0 < limit_s:
        await asyncio.sleep(0.25)
        if gauges()[key] != seen:
            seen, last_change = gauges()[key], time.perf_counter()
    note(f"derates at rest after {time.perf_counter() - t0:.1f} s ({seen:.0f} steps so far)")


async def _warm_full_solve(run) -> None:
    """A full solve seeded with the committed plan's potentials is another
    program than set-up's first (cold) one, and the daemons run it whenever
    a delta gate trips (every ninth solve at the latest): run it once here.
    Its moves go through the sources as the daemons' do: heartbeats have
    activated actors by now, and a raw directory write would leave one live
    where the directory no longer points."""
    c = run.cluster
    sink = c.servers[0].migration_manager.apply_moves
    for _ in range(5):
        with run.span("bench.warm.full_solve"):
            await c.placement.rebalance(delta=False, move_sink=sink)
        if not c.placement.stats.discarded:
            return
    run.failures.append("the warm full solve was discarded 5 times in a row")


async def warm(run, params) -> None:
    st = _state(run, params)
    members = st["members"]
    t0 = time.perf_counter()
    with run.span("bench.churn.bind_members"):
        for i in members.idx:
            if st["active"][i]:
                members.start(i, await members.bind(i))
    note(f"{len(members.servers)} members bound in {time.perf_counter() - t0:.1f} s")
    await _derates_at_rest(run)
    st["watcher"].start()
    ev = await _event(run, params, st, in_window=False)
    # Set-up may wait longer than the window would: the first delta solve
    # compiles the class refresh.
    served = await _await_served(ev, max(60.0, params["serve_limit_s"]))
    note(f"warm event served: {served} in {time.perf_counter() - ev['t_flip']:.2f} s")
    if not served:
        run.failures.append("the warm churn event was not served in set-up")
    await _warm_full_solve(run)
    # The window opens on a directory at rest, or the first event's `before`
    # is not the plan's.
    await _derates_at_rest(run, quiet_s=2.0, limit_s=10.0)


async def drive(run, params, t_start: float, t_end: float) -> None:
    c = run.cluster
    st = _state(run, params)
    rng = st["rng"]
    gauges = getattr(c.placement, "place_gauges", None)
    st["gauges0"] = dict(gauges()) if gauges is not None else {}
    k = 0
    while True:
        t_next = (
            t_start + params["first_s"] + k * params["period_s"]
            + float(rng.uniform(-params["jitter_s"], params["jitter_s"]))
        )
        if t_start + params["first_s"] + k * params["period_s"] >= t_end:
            break
        await asyncio.sleep(max(t_next - time.perf_counter(), 0.0))
        await _event(run, params, st, in_window=True)
        k += 1
    timed = [ev for ev in st["events"] if ev["in_window"]]
    for ev in timed:
        await _await_served(ev, params["serve_limit_s"])
    st["gauges1"] = dict(gauges()) if gauges is not None else {}
    st["watcher"].stop_event.set()
    run.log[params["name"]] = {
        "kind": "churn", "events": st["events"], "timed": len(timed),
        "serve_limit_s": params["serve_limit_s"],
        "gauges0": st["gauges0"], "gauges1": st["gauges1"],
        "descriptors_at_end": open_descriptors(),
        "final_counts": st["watcher"].counts,  # a callable: the audit reads it at rest
        "process_stops": list(st["watcher"].stops),
        "members": st["members"],
    }
