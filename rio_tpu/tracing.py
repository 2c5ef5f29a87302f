"""Request-path tracing spans.

Reference: the ``tracing`` crate spans on the hot path
(``rio-rs/src/service.rs:192,260,303,369``; ``registry/mod.rs:151-176``),
exported app-side via OpenTelemetry (observability example). Here: a
zero-dependency span API that records name, duration, and key/values; sinks
are pluggable (logging sink provided; an OTLP sink can be registered by the
application the same way the reference wires ``tracing_subscriber``).

Beside the per-request spans lives the **stage log** (:class:`stage`): one
always-on, bounded, process-wide record of COARSE host stages — a batch
call, a solve, a full garbage collection — never a request and never a
key. It is what tiles a directory call's wall time from inside the program.

Beside the stage log lives the **loop's own clock** (:func:`watch_loop`): a
5 ms tick on every event loop a ``LoadMonitor`` runs on, which logs each
stretch the loop could not turn (:func:`hold_log`, each named once from the
stage log) and how late ready callbacks run (:func:`tick_log`).
"""

from __future__ import annotations

import asyncio
import collections
import contextvars
import gc
import itertools
import logging
import os
import random
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

log = logging.getLogger("rio_tpu.trace")

_SINKS: list[Callable[["Span"], None]] = []
_ENABLED = False

# Active (trace_id, span_id), propagated through awaits by contextvars —
# the stand-in for the reference's nested `tracing` span contexts
# (service.rs:192-369): a request's placement→activate→dispatch spans all
# share one trace and point at their parent.
_CTX: contextvars.ContextVar[tuple[str, str] | None] = contextvars.ContextVar(
    "rio_tpu_trace", default=None
)
_rand = random.Random()

# Head-based probabilistic sampling for client-rooted traces: the client
# flips this coin ONCE per request with no active context; everything
# downstream (server adoption, forwarded hops) honors the decision carried
# on the wire instead of re-sampling.
_SAMPLE_RATE = 0.0


def _reseed() -> None:
    # An import-time-seeded Random is fork-hazardous: two workers forked
    # after import share the generator state and emit colliding trace/span
    # ids. Seed from the OS entropy pool, and re-seed in every forked child.
    _rand.seed(os.urandom(16))


_reseed()
if hasattr(os, "register_at_fork"):  # absent on non-POSIX
    os.register_at_fork(after_in_child=_reseed)


def current_trace_id() -> str | None:
    """The active trace id (e.g. to stamp application log lines)."""
    ctx = _CTX.get()
    return ctx[0] if ctx else None


def set_sample_rate(rate: float) -> None:
    """Probability that a client request with no active trace roots one."""
    global _SAMPLE_RATE
    _SAMPLE_RATE = min(1.0, max(0.0, rate))


def sample_rate() -> float:
    return _SAMPLE_RATE


def head_sampled() -> bool:
    """One head-based sampling decision (rate 0 short-circuits the coin)."""
    return _SAMPLE_RATE > 0.0 and _rand.random() < _SAMPLE_RATE


def new_trace_id() -> str:
    return f"{_rand.getrandbits(128):032x}"


def new_span_id() -> str:
    return f"{_rand.getrandbits(64):016x}"


def outbound_ctx() -> tuple[str, str, bool] | None:
    """The wire ``trace_ctx`` an outbound request should carry.

    The active span's ids when a trace is live (so the receiving node's
    spans join it), else ``None`` — the caller decides separately whether
    to root a fresh sampled trace (:func:`head_sampled`).
    """
    ctx = _CTX.get()
    if ctx is None:
        return None
    return (ctx[0], ctx[1], True)


def adopt(ctx: tuple[str, str, bool] | None):
    """Adopt an inbound wire ``trace_ctx`` for the current task.

    Returns a token for :func:`release` (``None`` when there is nothing to
    adopt — absent context or sampled=False). While adopted, spans opened
    here join the caller's trace and nested outbound sends forward it.
    """
    if ctx is None or not ctx[2]:
        return None
    return _CTX.set((ctx[0], ctx[1]))


def release(token) -> None:
    if token is not None:
        _CTX.reset(token)


@dataclass
class Span:
    name: str
    attrs: dict[str, Any] = field(default_factory=dict)
    start: float = 0.0
    duration: float = 0.0
    # W3C-style correlation ids (hex; 128-bit trace, 64-bit span). Filled
    # only on the sinked path — the null path never allocates ids.
    trace_id: str = ""
    span_id: str = ""
    parent_id: str = ""
    wall_start: float = 0.0  # unix seconds (exporters need wall clock)


def add_sink(sink: Callable[[Span], None]) -> None:
    """Register a span consumer (e.g. an OTLP exporter bridge)."""
    global _ENABLED
    _SINKS.append(sink)
    _ENABLED = True


def clear_sinks() -> None:
    global _ENABLED
    _SINKS.clear()
    _ENABLED = False


def enabled() -> bool:
    """True when at least one sink is registered (spans are live)."""
    return _ENABLED


def logging_sink(span: Span) -> None:
    log.debug("span %s %.3fms %s", span.name, span.duration * 1e3, span.attrs)


class _NullSpan:
    """Shared no-op context manager: zero allocation on the unsinked path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _LiveSpan:
    __slots__ = ("_span", "_token")

    def __init__(self, name: str, attrs: dict[str, Any]) -> None:
        self._span = Span(name=name, attrs=attrs)

    def __enter__(self) -> Span:
        s = self._span
        parent = _CTX.get()
        if parent is None:
            s.trace_id = f"{_rand.getrandbits(128):032x}"
        else:
            s.trace_id, s.parent_id = parent
        s.span_id = f"{_rand.getrandbits(64):016x}"
        self._token = _CTX.set((s.trace_id, s.span_id))
        s.wall_start = time.time()
        s.start = time.perf_counter()
        return s

    def __exit__(self, *exc) -> bool:
        s = self._span
        s.duration = time.perf_counter() - s.start
        _CTX.reset(self._token)
        for sink in _SINKS:
            try:
                sink(s)
            except Exception:  # sinks must never break the request path
                log.exception("trace sink failed")
        return False


def span(name: str, **attrs: Any):
    """Trace a block. Free (shared null object) when no sink is registered."""
    if not _ENABLED:
        return _NULL_SPAN
    return _LiveSpan(name, attrs)


# ---------------------------------------------------------------------------
# The stage log: coarse host stages, always on, bounded by construction
# ---------------------------------------------------------------------------

#: Records kept. A stage is entered per batch call, per solve or per full
#: collection (some tens a second at most), so the ring holds minutes.
STAGE_LOG_SIZE = 4096

# (name, t0_ns, t1_ns, parent, call_id, thread_id, wait): ``time.perf_counter_ns``
# stamps; ``parent`` is the enclosing stage's name (None at a root);
# ``call_id`` is shared by every stage under one root (0: none, a collection);
# ``wait`` says the stage timed a wait (its thread ran other work meanwhile).
_STAGE_LOG: collections.deque = collections.deque(maxlen=STAGE_LOG_SIZE)
# The ``with`` stages that have not ended yet (a set: add and discard are one
# step each, from any thread): what a hold's naming waits for.
_OPEN_STAGES: set = set()
# name -> [count, total_ns, max_ns], the operator's view (rio.stage.*).
_STAGE_TOTALS: dict[str, list[int]] = {}
# (Re-entrant: a collection that starts while it is held can finalize a
# monitor's pending task, whose ``finally`` takes it for ``unwatch_*``.)
_STAGE_LOCK = threading.RLock()
_STAGE_CTX: contextvars.ContextVar[tuple[str | None, int]] = contextvars.ContextVar(
    "rio_tpu_stage", default=(None, 0)
)
_CALL_IDS = itertools.count(1)


def _annotation(name: str):
    """A profiler annotation ``rio_tpu.<name>``, entered; None off-JAX.

    Only a process that already imported jax gets one: this module sits on
    the request path's imports and must never pull the accelerator stack in.
    """
    # (getattr: a collection may run while ``import jax`` is under way.)
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation("rio_tpu." + name)
    ann.__enter__()
    return ann


def _log_stage(
    name: str, t0: int, t1: int, parent: str | None, call_id: int, wait: bool = False
) -> None:
    _STAGE_LOG.append((name, t0, t1, parent, call_id, threading.get_ident(), wait))
    dur = t1 - t0
    with _STAGE_LOCK:
        row = _STAGE_TOTALS.get(name)
        if row is None:
            row = _STAGE_TOTALS[name] = [0, 0, 0]
        row[0] += 1
        row[1] += dur
        if dur > row[2]:
            row[2] = dur


class stage:
    """Time one coarse host stage: ``with stage("place.apply"): ...``.

    For work done per batch call, per solve, per full collection — NEVER per
    request or per key: every exit appends one record to the process-wide
    bounded log (:func:`stage_log`) and adds to the per-name totals that
    :func:`rio_tpu.otel.server_gauges` exports as ``rio.stage.<name>.*``.
    The stage is also a ``jax.profiler.TraceAnnotation("rio_tpu.<name>")``,
    so a profiler session shows it on the thread that ran it. Nesting and
    ``asyncio.to_thread`` carry the enclosing stage and the call id along
    (a contextvar). There is no switch: the cost is bounded by what may be
    a stage. ``wait=True`` where the block times a WAIT (a lock, the device):
    its thread runs other work meanwhile, so it names no hold of the loop.
    """

    __slots__ = ("name", "wait", "t0", "t1", "_parent", "_call", "_thread", "_token", "_ann")

    def __init__(self, name: str, *, wait: bool = False) -> None:
        self.name = name
        self.wait = wait
        self.t0 = self.t1 = 0

    def __enter__(self) -> "stage":
        parent, call = _STAGE_CTX.get()
        self._parent = parent
        self._call = call = call or next(_CALL_IDS)
        self._thread = threading.get_ident()
        self._token = _STAGE_CTX.set((self.name, call))
        self._ann = _annotation(self.name)
        _OPEN_STAGES.add(self)  # (``t0`` still 0: a reader skips it)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _STAGE_CTX.reset(self._token)
        _log_stage(self.name, self.t0, self.t1, self._parent, self._call, self.wait)
        _OPEN_STAGES.discard(self)  # after the record: never in neither
        return False

    @property
    def ms(self) -> float:
        """Duration in milliseconds (after exit), from the logged stamps."""
        return (self.t1 - self.t0) / 1e6


def stage_since(name: str, t0_ns: int) -> None:
    """Log a stage that began at ``t0_ns`` (another stage's ``t1``) and ends
    now, under the current stage: a wait whose start was stamped elsewhere
    (a worker thread's last instant) and so cannot be a ``with`` block."""
    parent, call = _STAGE_CTX.get()
    _log_stage(name, t0_ns, time.perf_counter_ns(), parent, call, True)


def stage_between(name: str, t0_ns: int, t1_ns: int, *, wait: bool = False) -> None:
    """Log a stage from two stamps taken elsewhere, under the current stage:
    one record that stands for many (the slowest of a plan's bursts, a
    ``wait``), or work whose end decides whether it is logged at all."""
    parent, call = _STAGE_CTX.get()
    _log_stage(name, t0_ns, t1_ns, parent, call, wait)


def stage_log() -> list[tuple]:
    """A snapshot of the log, oldest first."""
    return list(_STAGE_LOG)


def stage_totals() -> dict[str, tuple[int, int, int]]:
    """``name -> (count, total_ns, max_ns)`` since the process started."""
    with _STAGE_LOCK:
        return {k: (v[0], v[1], v[2]) for k, v in _STAGE_TOTALS.items() if v[0]}


def stage_gauges() -> dict[str, float]:
    """The totals in the :func:`rio_tpu.otel.stats_gauges` shape."""
    out: dict[str, float] = {}
    for name, (count, total_ns, max_ns) in stage_totals().items():
        p = f"rio.stage.{name}"
        out[f"{p}.count"] = float(count)
        out[f"{p}.total_ms"] = total_ns / 1e6
        out[f"{p}.max_ms"] = max_ns / 1e6
    return out


def clear_stages() -> None:
    """Forget every record and total, the loop's holds and roll-up rows among
    them (tests). A running tick keeps its own counts."""
    with _STAGE_LOCK:
        _STAGE_LOG.clear()
        _HOLD_NEW.clear()
        _HOLD_WAITING.clear()
        _HOLD_LOG.clear()
        _HOLD_BY_CAUSE.clear()
        _TICK_LOG.clear()
        for row in _STAGE_TOTALS.values():
            row[:] = [0, 0, 0]


# The interpreter's collections ride the same log: a full one (generation 2)
# stops every thread, so it is a stage, ``gc.gen2``; the young generations
# only add to their totals. One ``gc.callbacks`` entry per process, held
# while any LoadMonitor runs. The rows are seated here so that the callback,
# which runs at any allocation, inserts nothing and takes no lock
# (collections do not nest: one writer).
#
# While the entry is held the old heap SETTLES: what a full collection kept
# is moved to the interpreter's permanent generation when it stops
# (``gc.freeze()``: three list merges), so the next one walks only what was
# allocated since, not the modules, servers and actors it has walked before.
# Only survivors of a full collection are ever set aside, they are still
# freed by reference count, and a cycle that dies among them waits for the
# next WHOLE walk: when the settled heap has doubled since the last one the
# ``start`` of a full collection thaws it first (the callback runs before
# the collector gathers its lists, so that collection covers it). Nothing
# here is O(heap) except a whole walk and the count that decides on one.
_GC_ROWS = [_STAGE_TOTALS.setdefault(f"gc.gen{g}", [0, 0, 0]) for g in range(3)]
_GC_OPEN: list = [0, None, False]  # start stamp, the full collection's annotation, a whole walk
# Objects the last whole walk kept at the most (or a later exact count, if
# lower); an upper bound on what is settled now (each full collection adds
# the survivors it walked; what reference counts freed since is still in
# it); the bound at which to count exactly again.
_GC_HEAP = [0, 0, 0]
_GC_WALKS = [0, 0]  # full collections that walked the young heap only, whole walks
_GC_WATCHERS = 0


def _on_gc(phase: str, info: dict) -> None:
    gen = info["generation"]
    if phase == "start":
        _GC_OPEN[0] = time.perf_counter_ns()
        if gen == 2:
            _GC_OPEN[1] = _annotation("gc.gen2")
            kept, bound, recount_at = _GC_HEAP
            if bound >= recount_at:
                # The exact count walks the permanent generation's list, so
                # it is taken once per half of ``kept`` objects settled.
                bound = gc.get_freeze_count()
                if bound >= 2 * kept:
                    # A whole walk keeps at most what is settled and what is
                    # young now (counted before the thaw: O(young)).
                    kept = bound + len(gc.get_objects())
                    _GC_HEAP[:] = kept, kept, 2 * kept
                    gc.unfreeze()
                    _GC_OPEN[2] = True
                else:
                    # (A heap that shrank since is the one to double.)
                    kept = min(kept, bound)
                    _GC_HEAP[:] = kept, bound, max(2 * kept, bound + kept // 2)
        return
    if gen == 2:
        if _GC_OPEN[2]:
            _GC_OPEN[2] = False
            _GC_WALKS[1] += 1
        else:
            # After a full collection every tracked object that is not
            # settled is a survivor of it: O(young).
            _GC_HEAP[1] += len(gc.get_objects())
            _GC_WALKS[0] += 1
        gc.freeze()
    t1 = time.perf_counter_ns()
    t0 = _GC_OPEN[0]
    if gen == 2:
        ann, _GC_OPEN[1] = _GC_OPEN[1], None
        if ann is not None:
            ann.__exit__(None, None, None)
        _STAGE_LOG.append(("gc.gen2", t0, t1, None, 0, threading.get_ident(), False))
    row = _GC_ROWS[gen]
    row[0] += 1
    row[1] += t1 - t0
    if t1 - t0 > row[2]:
        row[2] = t1 - t0


def gc_gauges() -> dict[str, float]:
    """How the old heap settles (``rio.gc.*``): full collections that walked
    only the young heap, those that thawed and walked everything, and the
    bound on the settled objects as last corrected (never a walk)."""
    return {
        "rio.gc.settled": float(_GC_WALKS[0]),
        "rio.gc.whole_walks": float(_GC_WALKS[1]),
        "rio.gc.settled_objects": float(_GC_HEAP[1]),
    }


def watch_gc() -> None:
    """Install the collection callback (the first caller does; counted)."""
    global _GC_WATCHERS
    with _STAGE_LOCK:
        _GC_WATCHERS += 1
        if _GC_WATCHERS == 1:
            gc.callbacks.append(_on_gc)


def unwatch_gc() -> None:
    """Undo one :func:`watch_gc`; the last caller removes the callback and
    thaws what it set aside: the process has the collector it started with."""
    global _GC_WATCHERS
    with _STAGE_LOCK:
        if _GC_WATCHERS == 0:
            return
        _GC_WATCHERS -= 1
        if _GC_WATCHERS == 0 and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
            gc.unfreeze()
            _GC_HEAP[:] = [0, 0, 0]


# ---------------------------------------------------------------------------
# The loop's own clock: a tick that logs every stretch the loop could not turn
# ---------------------------------------------------------------------------

#: The tick's period, and how late a tick has to run to be logged as a hold.
#: Constants, not options: 200 callbacks a second on a loop that runs
#: thousands, and a threshold of two ticks so that the selector's own
#: millisecond rounding never reads as a hold.
LOOP_TICK_NS = 5_000_000
HOLD_MIN_NS = 10_000_000
#: Every so many ticks one roll-up row; rows and holds kept.
TICK_ROLLUP = 200
TICK_LOG_SIZE = 1024
HOLD_LOG_SIZE = 16384

# (due_ns, now_ns, loop_thread_id): the tick due at ``due_ns`` ran at
# ``now_ns``; the loop did not turn in between. The tick appends, the next
# read takes them out and names them.
_HOLD_NEW: collections.deque = collections.deque(maxlen=HOLD_LOG_SIZE)
# Taken out and not named for good yet: a stage that began before the hold's
# end is still open (readers only, under ``_STAGE_LOCK``).
_HOLD_WAITING: list = []
# (t0_ns, t1_ns, cause, named_ns): every hold, named ONCE, and the time by
# cause of all that were ever named.
_HOLD_LOG: collections.deque = collections.deque(maxlen=HOLD_LOG_SIZE)
_HOLD_BY_CAUSE: dict[str, int] = {}
# (now_ns, ticks, late_ns, loop_thread_id), the loop's cumulative counts.
_TICK_LOG: collections.deque = collections.deque(maxlen=TICK_LOG_SIZE)
_LOOP_TICKS: dict = {}  # running loop -> its _LoopTick
# ticks, late_ns, holds, hold_ns, hold_max_ns of the chains that have stopped.
_LOOP_STOPPED = [0, 0, 0, 0, 0]


class _LoopTick:
    """One loop's ``call_at`` chain. Only the loop's thread writes it."""

    __slots__ = (
        "loop", "watchers", "handle", "due", "thread_id",
        "ticks", "late_ns", "holds", "hold_ns", "hold_max_ns",
    )

    def __init__(self, loop: asyncio.AbstractEventLoop) -> None:
        self.loop = loop
        self.watchers = 0
        self.thread_id = threading.get_ident()
        self.ticks = self.late_ns = self.holds = self.hold_ns = self.hold_max_ns = 0
        self.due = time.perf_counter_ns() + LOOP_TICK_NS
        self.handle = loop.call_at(loop.time() + LOOP_TICK_NS / 1e9, self._tick)

    def counts(self) -> tuple[int, int, int, int, int]:
        return self.ticks, self.late_ns, self.holds, self.hold_ns, self.hold_max_ns

    def _tick(self) -> None:
        now = time.perf_counter_ns()
        lag = max(0, now - self.due)
        self.ticks += 1
        self.late_ns += lag
        if lag >= HOLD_MIN_NS:
            _HOLD_NEW.append((self.due, now, self.thread_id))
            self.holds += 1
            self.hold_ns += lag
            if lag > self.hold_max_ns:
                self.hold_max_ns = lag
        if not self.ticks % TICK_ROLLUP:
            _TICK_LOG.append((now, self.ticks, self.late_ns, self.thread_id))
        # From its own due time, so that the period does not drift; from now
        # when it ran over a tick late: a hold is followed by no burst.
        self.due = due = (self.due if lag < LOOP_TICK_NS else now) + LOOP_TICK_NS
        self.handle = self.loop.call_at(self.loop.time() + (due - now) / 1e9, self._tick)


def _stop_tick(loop) -> None:
    """Cancel the loop's chain and keep its counts (under ``_STAGE_LOCK``)."""
    chain = _LOOP_TICKS.pop(loop)
    chain.handle.cancel()
    *sums, longest = chain.counts()
    _LOOP_STOPPED[:4] = [had + n for had, n in zip(_LOOP_STOPPED, sums)]
    _LOOP_STOPPED[4] = max(_LOOP_STOPPED[4], longest)


def watch_loop() -> None:
    """Start the running loop's tick (the first caller on a loop does;
    counted per loop). Call it from the loop's own thread."""
    loop = asyncio.get_running_loop()
    with _STAGE_LOCK:
        # (A loop that was closed under its monitors never called back.)
        for closed in [other for other in list(_LOOP_TICKS) if other.is_closed()]:
            _stop_tick(closed)
        chain = _LOOP_TICKS.get(loop)
        if chain is None:
            chain = _LOOP_TICKS[loop] = _LoopTick(loop)
        chain.watchers += 1


def unwatch_loop() -> None:
    """Undo one :func:`watch_loop` on the running loop; the last caller stops
    the chain: no callback of it runs afterwards."""
    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:  # a monitor's task finalized after its loop: nothing ticks
        return
    with _STAGE_LOCK:
        chain = _LOOP_TICKS.get(loop)
        if chain is None:
            return
        chain.watchers -= 1
        if chain.watchers <= 0:
            _stop_tick(loop)


def tick_log() -> list[tuple[int, int, int, int]]:
    """The roll-up rows, oldest first: ``(now_ns, ticks, late_ns,
    loop_thread_id)``, one every ``TICK_ROLLUP`` ticks of a loop, the counts
    cumulative for that loop's chain. ``late_ns / ticks`` between two rows is
    the mean wait of a ready callback for its turn."""
    return list(_TICK_LOG)


def _union_ns(intervals: list) -> int:
    total, end = 0, 0
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def _name_hold(t0: int, t1: int, loop_thread: int, holders: list) -> tuple[str, int]:
    """The cause of one hold and what of it the cause's stages cover."""
    own: dict = {}
    beside: dict = {}
    for name, a, b, _parent, _call, thread, _wait in holders:
        if b > t0 and a < t1:
            held = own if thread == loop_thread or name == "gc.gen2" else beside
            held.setdefault(name, []).append((max(a, t0), min(b, t1)))
    named = _union_ns([iv for ivs in own.values() for iv in ivs])
    if 2 * named >= t1 - t0:
        return max(own, key=lambda n: _union_ns(own[n])), named
    starved = {n: _union_ns(ivs) for n, ivs in beside.items()}
    starver = max(starved, key=starved.get, default=None)
    if starver is not None and 2 * starved[starver] >= t1 - t0:
        return "~" + starver, _union_ns([iv for ivs in (*own.values(), beside[starver]) for iv in ivs])
    return "unnamed", named


def _name_new_holds(rest: bool = False) -> list:
    """Name the holds that arrived since the last read: each ONCE, for good,
    at the first read at which every stage that began before its end has
    ended. With ``rest``, returns the others under the name they would get
    now."""
    with _STAGE_LOCK:
        while _HOLD_NEW:
            try:
                _HOLD_WAITING.append(_HOLD_NEW.popleft())
            except IndexError:  # another reader was faster (``clear_stages``)
                break
        if not _HOLD_WAITING:
            return []
        now = time.perf_counter_ns()
        still_open = [
            (s.name, s.t0, now, s._parent, s._call, s._thread, False)
            for s in list(_OPEN_STAGES) if s.t0 and not s.wait
        ]
        settled = min((r[1] for r in still_open), default=now)
        # (A stage that never ends holds nothing back beyond a ring's worth.)
        overdue = len(_HOLD_WAITING) - HOLD_LOG_SIZE
        final = [i < overdue or h[1] <= settled for i, h in enumerate(_HOLD_WAITING)]
        if not rest and not any(final):
            return []
        since = min(h[0] for h, done in zip(_HOLD_WAITING, final) if done or rest)
        recs = list(_STAGE_LOG) + still_open
        # What can name a hold: stages that time work, ended after the oldest
        # of these holds began, and leaves (no record of the same call names
        # them as its parent).
        leaves = {(r[4], r[0]) for r in recs if r[2] > since and not r[6]}
        leaves.difference_update([(r[4], r[3]) for r in recs if r[3] is not None])
        holders = [r for r in recs if r[2] > since and not r[6] and (r[4], r[0]) in leaves]
        names = [
            (h[0], h[1], *_name_hold(*h, holders)) if done or rest else None
            for h, done in zip(_HOLD_WAITING, final)
        ]
        for (t0, t1, cause, _named_ns) in (n for n, done in zip(names, final) if done):
            _HOLD_BY_CAUSE[cause] = _HOLD_BY_CAUSE.get(cause, 0) + t1 - t0
        _HOLD_LOG.extend(n for n, done in zip(names, final) if done)
        _HOLD_WAITING[:] = [h for h, done in zip(_HOLD_WAITING, final) if not done]
        return [n for n, done in zip(names, final) if not done] if rest else []


def hold_log() -> list[tuple[int, int, str, int]]:
    """``(t0_ns, t1_ns, cause, named_ns)`` per kept hold, oldest first.

    The cause is the leaf stage on the loop's own thread, or ``gc.gen2`` on
    any thread, with the largest overlap; where those cover under half of the
    hold, the stage on ANOTHER thread that overlaps half of it or more, as
    ``~<stage>``: the loop was starved beside that code (the interpreter
    lock), not held by it; else ``unnamed``. Stages that time a wait name
    nothing. ``named_ns`` is the union of the overlaps that count towards the
    cause. A hold is joined against the stage log ONCE, by the first read
    (this, or a scrape of :func:`loop_gauges`) after the stages that were
    open at its end have ended, and keeps that name; one that still waits
    for such a stage comes last, under the name it would get now.
    """
    waiting = _name_new_holds(rest=True)
    return list(_HOLD_LOG) + waiting


def loop_gauges() -> dict[str, float]:
    """The loop's clock as gauges (``rio.loop.*``), every loop of the process
    together, all cumulative: ``ticks`` and ``late_ms`` (their ratio is the
    mean wait of a ready callback for its turn: loop saturation),
    ``hold.{count,total_ms,max_ms}`` (stretches of ``HOLD_MIN_NS`` or more in
    which the loop did not turn) and ``hold.by.<cause>.ms`` (which subsystem
    to look at; ``~<stage>`` is starvation by a worker thread that holds the
    interpreter lock), which trails ``total_ms`` by the holds whose stages
    are still open."""
    _name_new_holds()
    with _STAGE_LOCK:
        counts = [c.counts() for c in list(_LOOP_TICKS.values())] + [tuple(_LOOP_STOPPED)]
        by_cause = dict(_HOLD_BY_CAUSE)
    ticks, late_ns, holds, hold_ns = (sum(c[i] for c in counts) for i in range(4))
    out = {
        "rio.loop.ticks": float(ticks),
        "rio.loop.late_ms": late_ns / 1e6,
        "rio.loop.hold.count": float(holds),
        "rio.loop.hold.total_ms": hold_ns / 1e6,
        "rio.loop.hold.max_ms": max(c[4] for c in counts) / 1e6,
    }
    out.update((f"rio.loop.hold.by.{cause}.ms", ns / 1e6) for cause, ns in by_cause.items())
    return out
