"""What every cell shares: finding files by name, the cluster wiring, the
directory reads, the quiescence wait, compile accounting and the run record.

The wiring is a copy of ``chip_smoke.py``'s (8 default ``Server``s on
loopback TCP over one ``JaxObjectPlacement(mode="auto")``, directory-only
members as rows in ``LocalStorage`` with addresses nobody listens on), kept
here so that a later PR may change the program and not the yardstick. Of
the program it imports the public entry points only.
"""

import asyncio
import contextlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
T0 = time.perf_counter()  # the process's start, near enough: run.py imports this first


def note(msg: str) -> None:
    print(f"# [{time.perf_counter() - T0:7.2f}s] {msg}", file=sys.stderr, flush=True)


def emit(record: dict) -> None:
    """An earlier line of standard output (the last one is the result)."""
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------


def bench_paths(bench: dict) -> list[Path]:
    return [REPO / p for p in bench["paths"]]


def find_file(bench: dict, sub: str, name: str, suffixes=(".py",)) -> Path:
    """``<path>/<sub>/<name><suffix>`` in the first of ``paths`` that has it."""
    for root in bench_paths(bench):
        for suffix in suffixes:
            p = root / sub / f"{name}{suffix}"
            if p.is_file():
                return p
    raise FileNotFoundError(f"no {sub}/{name}{suffixes} under {bench['paths']}")


_MODULES: dict = {}


def load_module(path: Path):
    """Import a file by path (names hold dots and dashes, so not by name)."""
    path = path.resolve()
    mod = _MODULES.get(path)
    if mod is None:
        tag = "_bench_" + "".join(c if c.isalnum() else "_" for c in str(path.relative_to(REPO)))
        spec = importlib.util.spec_from_file_location(tag, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[tag] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return mod


def plugin(bench: dict, sub: str, name: str):
    return load_module(find_file(bench, sub, name))


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Compile accounting (jax.monitoring)
# ---------------------------------------------------------------------------

COMPILES = {"backend_compile_s": 0.0, "backend_compiles": 0, "cache_hits": 0, "cache_misses": 0}


def watch_compiles() -> None:
    import jax

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILES["backend_compile_s"] += duration
            COMPILES["backend_compiles"] += 1

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            COMPILES["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILES["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


# The interpreter's garbage collections, by generation: a full one walks
# every container the process holds and stops the loop the servers share.
GC_PAUSES = {"count": [0, 0, 0], "sum_ms": [0.0, 0.0, 0.0], "max_ms": [0.0, 0.0, 0.0]}


def watch_gc() -> None:
    import gc

    started = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter()
            return
        g, ms = info["generation"], (time.perf_counter() - started[0]) * 1e3
        GC_PAUSES["count"][g] += 1
        GC_PAUSES["sum_ms"][g] += ms
        GC_PAUSES["max_ms"][g] = max(GC_PAUSES["max_ms"][g], ms)

    gc.callbacks.append(on_gc)


def gc_snapshot() -> dict:
    return {k: list(v) for k, v in GC_PAUSES.items()}


# ---------------------------------------------------------------------------
# The run record
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """One run of one cell: what the generators, audits and readers share."""

    args: object
    bench: dict
    cell: dict
    config: dict
    mix: dict
    app: object = None
    cluster: "Cluster" = None
    rehearsal: bool = False
    log: dict = field(default_factory=dict)  # what generators and set-up collected
    spans: list = field(default_factory=list)  # (name, t0_ns, t1_ns) by perf_counter_ns
    checks: list = field(default_factory=list)  # numbers compared, each beside its limit
    window: tuple = (0.0, 0.0)  # perf_counter at the window's start and end
    trace: dict | None = None  # trace_reduce's output on a --trace 1 run
    setup_s: float = 0.0
    failures: list = field(default_factory=list)  # reasons `correct` is false beyond a check

    def rng(self, stream: str) -> np.random.Generator:
        """A generator per named stream, all from ``--seed``."""
        words = [self.args.seed] + [ord(c) for c in stream]
        return np.random.default_rng(np.random.SeedSequence(words))

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter_ns()))

    def check(self, name: str, value, limit, ok: bool | None = None) -> bool:
        """Record one number compared, print it beside its limit."""
        ok = bool(value <= limit) if ok is None else bool(ok)
        rec = {"check": name, "value": value, "limit": limit, "ok": ok}
        self.checks.append(rec)
        emit(rec)
        return ok


# ---------------------------------------------------------------------------
# The cluster
# ---------------------------------------------------------------------------


def run_sync(coro):
    """Run a coroutine that never suspends, without an event loop."""
    try:
        coro.send(None)
    except StopIteration as e:
        return e.value
    coro.close()
    raise RuntimeError("coroutine suspended; it needs an event loop")


class Cluster:
    """Live servers, directory-only rows, one shared directory, one client."""

    def __init__(self) -> None:
        self.members = None
        self.placement = None
        self.servers: list = []
        self.tasks: list = []
        self.live: list[str] = []
        self.node_order: list[str] = []
        self.index_of: dict[str, int] = {}
        self.live_idx = np.zeros((0,), np.int64)
        self.client = None
        self.fanout_client = None
        # Names in a NumPy object array, not a list of ObjectIds: what the
        # harness holds must not be walked by every full collection of the
        # interpreter's garbage (a million dataclass instances cost 0.1 s
        # per 131,072 a collection, a list of a million strings 14 ms; an
        # object array is not walked at all), or the servers on this loop
        # are charged with the harness's stalls. PERF.md, Findings.
        self.tname = ""
        self.names = np.zeros((0,), object)
        self.state_path: str | None = None
        self.request_timeout = 5.0

    # -- directory reads (in a thread: the servers share the event loop, and
    # a second of bulk bookkeeping on it reads as load on every one of them)

    def oid(self, name: str):
        from rio_tpu import ObjectId

        return ObjectId(self.tname, name)

    def make_ids(self, names) -> list:
        from rio_tpu import ObjectId

        tname = self.tname
        return [ObjectId(tname, n) for n in names]

    def seats_sync(self, names) -> np.ndarray:
        """Node index per name through ``lookup_batch``; -1 where unseated."""
        addrs = run_sync(self.placement.lookup_batch(self.make_ids(names)))
        index_of = self.index_of
        return np.fromiter(
            (-1 if a is None else index_of[a] for a in addrs), np.int64, count=len(addrs)
        )

    async def seats(self, names=None) -> np.ndarray:
        return await asyncio.to_thread(self.seats_sync, self.names if names is None else names)

    def counts(self, seats: np.ndarray) -> np.ndarray:
        return np.bincount(seats[seats >= 0], minlength=len(self.node_order))

    async def active_mask(self) -> np.ndarray:
        active = {m.address for m in await self.members.members() if m.active}
        return np.array([a in active for a in self.node_order], bool)

    def full_idx(self, active: np.ndarray) -> np.ndarray:
        """Active directory-only members: they report no load, so their
        capacity is 1 in every solve."""
        full = active.copy()
        full[self.live_idx] = False
        return np.nonzero(full)[0]

    def daemons(self) -> list:
        return [s.placement_daemon for s in self.servers if s.placement_daemon is not None]

    def daemon_totals(self) -> dict:
        out: dict = {}
        for d in self.daemons():
            for k, v in vars(d.stats).items():
                out[k] = out.get(k, 0) + v
        return out

    def red_rows(self) -> dict:
        """Merged RED histograms of the live servers, as plain rows."""
        from rio_tpu.metrics import merge_rows

        sets = [
            s.metrics_registry.snapshot_rows()
            for s in self.servers
            if s.metrics_registry is not None
        ]
        return {k: (h.count, list(h.buckets)) for k, h in merge_rows(sets).items()}

    async def close(self) -> None:
        for client in (self.client, self.fanout_client):
            if client is not None:
                client.close()
        for t in self.tasks:
            t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)


async def build_cluster(run: Run) -> Cluster:
    from rio_tpu import AppData, Client, LocalStorage, ObjectId, Server
    from rio_tpu.cluster.membership_protocol import LocalClusterProvider
    from rio_tpu.cluster.storage import Member
    from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

    cfg = run.config
    c = Cluster()
    c.request_timeout = float(cfg.get("request_timeout_s", 5.0))
    c.members = LocalStorage()
    n_dir = cfg["nodes"] - cfg["live_servers"]
    # Directory-only members are rows in the storage the daemons read
    # (sync_members marks a node absent from the list dead). Their loopback
    # addresses have no listener; no cell plans a move that would dial one.
    dir_nodes = [f"127.77.{i // 250}.{i % 250 + 1}:7000" for i in range(n_dir)]
    # Members that are down when the run starts (a cluster under churn is
    # never whole): rows like the others, never seated on.
    down = set(
        run.rng("down_at_start").choice(n_dir, cfg.get("down_at_start", 0), replace=False).tolist()
    )
    for i, addr in enumerate(dir_nodes):
        await c.members.push(Member.from_address(addr, active=i not in down))
    c.placement = JaxObjectPlacement(mode=cfg.get("placement_mode", "auto"))
    state = None
    if cfg.get("state") == "sqlite":
        from rio_tpu.state import StateProvider
        from rio_tpu.state.sqlite import SqliteState

        tmp = Path(os.environ.get("TMPDIR") or "/tmp") / f"rio-bench-{os.getpid()}"
        tmp.mkdir(parents=True, exist_ok=True)
        c.state_path = str(tmp / "state.db")
        for leftover in tmp.glob("state.db*"):
            leftover.unlink()
        state = SqliteState(c.state_path)
        await state.prepare()
    placement = c.placement

    def resolver(t, i):
        return placement.lookup(ObjectId(t, i))

    fanout = None
    if getattr(run.app, "FANOUT_CLIENT", False):
        fanout = c.fanout_client = Client(c.members, placement_resolver=resolver)
    for _ in range(cfg["live_servers"]):
        app_data = AppData()
        if state is not None:
            app_data.set(state, as_type=StateProvider)
        if fanout is not None:
            app_data.set(fanout)
        s = Server(
            address="127.0.0.1:0",
            registry=run.app.registry(),
            cluster_provider=LocalClusterProvider(c.members),
            object_placement_provider=c.placement,
            app_data=app_data,
            placement_daemon=True,
        )
        await s.prepare()
        await s.bind()
        c.servers.append(s)
    c.tasks = [asyncio.create_task(s.run()) for s in c.servers]
    c.live = [s.local_address for s in c.servers]
    for _ in range(400):
        if {m.address for m in await c.members.active_members()} >= set(c.live):
            break
        await asyncio.sleep(0.05)
    else:
        raise RuntimeError("servers never registered in membership")
    c.placement.sync_members(await c.members.members())
    # Each daemon treats the servers that registered after its first poll
    # as churn and re-solves the (still empty) directory; let that pass.
    for _ in range(1200):
        if all(d.stats.polls >= 3 for d in c.daemons()) and len(c.daemons()) == len(c.servers):
            break
        await asyncio.sleep(0.05)
    else:
        raise RuntimeError("the placement daemons never started polling")
    c.node_order = list(c.placement._node_order)  # index -> address, as chip_smoke reads it
    c.index_of = {a: i for i, a in enumerate(c.node_order)}
    if len(c.node_order) != cfg["nodes"]:
        raise RuntimeError(f"{len(c.node_order)} directory nodes, want {cfg['nodes']}")
    c.live_idx = np.array([c.index_of[a] for a in c.live], np.int64)
    c.client = Client(c.members, placement_resolver=resolver)
    c.tname = run.app.TYPE
    c.names = np.array(await asyncio.to_thread(run.app.object_names, cfg), object)
    return c


async def seat_all(run: Run) -> None:
    """The whole directory through ``assign_batch``, then the first full
    solve, which commits the plan that delta solves run against."""
    c = run.cluster
    with run.span("bench.setup.assign_batch"):
        t0 = time.perf_counter()
        addrs = await c.placement.assign_batch(await asyncio.to_thread(c.make_ids, c.names))
        run.log["seat_s"] = time.perf_counter() - t0
    if len(addrs) != len(c.names) or c.placement.count() != len(c.names):
        raise RuntimeError("assign_batch did not seat every object")
    run.log["first_solve"] = await full_solve(run, "bench.setup.full_solve")


async def full_solve(run: Run, span_name: str) -> dict:
    """One committed ``rebalance(delta=False)``, called as ``chip_smoke.py``
    calls it (raw directory writes, no hand-offs)."""
    c = run.cluster
    want = run.config.get("solve_mode", {}).get("cpu" if run.rehearsal else "tpu")
    for attempt in range(5):
        with run.span(span_name):
            await c.placement.rebalance(delta=False)
        st = c.placement.stats
        if not st.discarded:
            break
        note(f"{span_name}: attempt {attempt + 1} lost an epoch race, retrying")
    else:
        raise RuntimeError(f"{span_name}: 5 solves in a row were discarded")
    if want and st.mode != want:
        raise RuntimeError(f"full solve ran as {st.mode!r}, the configuration says {want!r}")
    return {"mode": st.mode, "solve_ms": st.solve_ms, "compile_ms": st.compile_ms,
            "exec_ms": st.exec_ms, "apply_ms": st.apply_ms, "moved": st.moved}


# ---------------------------------------------------------------------------
# Quiescence (rule 1: the verdict is taken after the program has come to rest)
# ---------------------------------------------------------------------------

# The daemons' discard ladder is 1+2+4+8+16 s (min_rebalance_interval * 2^k,
# max_discard_retries=5); the wait outlasts it with room for the solves.
QUIESCE_LIMIT_S = 45.0
QUIESCE_STABLE_S = 2.0  # two daemon poll intervals


async def quiesce(run: Run) -> bool:
    """Wait, outside the window, until no object sits on an inactive node,
    no daemon has a retry armed, and neither the daemons' counters nor the
    committed-solve record have moved for two poll intervals."""
    c = run.cluster
    t0 = time.perf_counter()
    last = None
    stable_since = t0
    on_inactive = -1
    while True:
        now = time.perf_counter()
        totals = c.daemon_totals()
        totals.pop("polls", None)
        totals.pop("load_syncs", None)
        totals.pop("kicks", None)
        snap = (tuple(sorted(totals.items())), id(c.placement.stats))
        if snap != last:
            last, stable_since = snap, now
        # A private flag, read for the WAIT only: it can lengthen the wait,
        # never decide a bound.
        armed = sum(bool(getattr(d, "_retry_solve", False)) for d in c.daemons())
        if now - stable_since >= QUIESCE_STABLE_S and not armed:
            seats = await c.seats()
            active = await c.active_mask()
            on_inactive = int((~active[seats[seats >= 0]]).sum())
            if on_inactive == 0:
                break
        if now - t0 > QUIESCE_LIMIT_S:
            abandoned = c.daemon_totals().get("retries_abandoned", 0)
            reason = (
                f"no quiescence in {QUIESCE_LIMIT_S:.0f} s: {on_inactive} seats on "
                f"inactive nodes, {armed} daemons with a retry armed, "
                f"{abandoned} retries abandoned"
            )
            emit({"quiesce": "failed", "reason": reason})
            run.failures.append(reason)
            return False
        await asyncio.sleep(0.25)
    run.log["quiesce_s"] = time.perf_counter() - t0
    emit({"quiesce": "ok", "seconds": run.log["quiesce_s"]})
    return True


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------


def device_record() -> dict:
    import jax

    devices = jax.devices()
    peak = 0
    for d in devices:
        peak = max(peak, int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
