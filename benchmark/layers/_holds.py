"""What the readers of the loop's own clock share: the hold log
(``rio_tpu.tracing.hold_log``: every stretch of 10 ms or more in which the
servers' event loop did not turn, named from the stage log) and the tick
roll-up (``tick_log``: cumulative ticks and how late they ran), both on
``perf_counter_ns``, the clock of ``run.window`` and ``run.spans``.

A hold is ``(t0_ns, t1_ns, cause, named_ns)``, a roll-up row ``(now_ns,
ticks, late_ns, loop_thread_id)``. Everything returns ``None`` where the
program keeps no such log (a commit from before it), where no tick ran over
the window, and where a ring is full and its oldest record is younger than
the window's start: never a short sum. Everything counts the WINDOW alone:
the readers run in traced runs, where the harness stops the profiler on the
servers' loop right after the window (seconds in which no tick runs), and
the tick due at the window's last instant carries all of that.
"""

import numpy as np

from benchmark.harness import note, plugin

UNNAMED = "unnamed"
TICK_NS = 5_000_000  # the tick's period: two holds this close are one tick apart


def window_ns(run) -> tuple:
    return plugin(run.bench, "layers", "_stages").window_ns(run)


def tick_rows(run) -> dict | None:
    """Per loop, its roll-up rows from the last at or before the window's
    start to the first at or after its end; None where no loop's rows
    bracket it."""
    from rio_tpu import tracing

    log = getattr(tracing, "tick_log", None)
    if log is None:
        return None
    lo, hi = window_ns(run)
    by_loop: dict = {}
    for row in log():
        by_loop.setdefault(row[3], []).append(row)
    out = {}
    for loop, rows in by_loop.items():
        before = [i for i, r in enumerate(rows) if r[0] <= lo]
        after = [i for i, r in enumerate(rows) if r[0] >= hi]
        if before and after:
            out[loop] = rows[before[-1] : after[0] + 1]
    return out or None


def every_hold(run) -> list | None:
    """The hold log, whole; None unless it reaches back to the roll-up row
    before the window's start."""
    from rio_tpu import tracing

    log, rows = getattr(tracing, "hold_log", None), tick_rows(run)
    if log is None or rows is None:
        return None
    found = log()
    if len(found) >= tracing.HOLD_LOG_SIZE and found[0][0] > min(r[0][0] for r in rows.values()):
        return None
    return found


def holds(run) -> list | None:
    """The holds that overlap the window, whole (``clipped`` cuts them)."""
    found = every_hold(run)
    if found is None:
        return None
    lo, hi = window_ns(run)
    return [h for h in found if h[1] > lo and h[0] < hi]


def clipped(found, lo: int, hi: int) -> list:
    return [(max(h[0], lo), min(h[1], hi)) for h in found if h[1] > lo and h[0] < hi]


def hold_ms_per_s(run) -> float | None:
    found = holds(run)
    if found is None:
        return None
    union = plugin(run.bench, "layers", "_stages").union_ns
    return union(clipped(found, *window_ns(run))) / 1e6 / (run.window[1] - run.window[0])


def hold_max_ms(run) -> float | None:
    """The longest hold that BEGAN in the window, as far as it lies in it (0
    where none did): one that the harness's own work after the window's last
    instant makes, and whose tick was due just before it, reads what of it
    the window's requests could feel."""
    found = holds(run)
    if found is None:
        return None
    lo, hi = window_ns(run)
    return max((min(h[1], hi) - h[0] for h in found if lo <= h[0] < hi), default=0) / 1e6


def unnamed_ns(hold, lo: int, hi: int) -> int:
    """What of a hold no stage names, inside ``lo..hi``: all of an unnamed
    hold, the remainder of a named one (its share of the clipped part)."""
    t0, t1, cause, named = hold
    inside = min(t1, hi) - max(t0, lo)
    if cause == UNNAMED:
        return inside
    return round(inside * (t1 - t0 - named) / (t1 - t0))


def hold_unnamed_ms_per_s(run) -> float | None:
    found = holds(run)
    if found is None:
        return None
    lo, hi = window_ns(run)
    return sum(unnamed_ns(h, lo, hi) for h in found) / 1e6 / (run.window[1] - run.window[0])


def slowest(run) -> np.ndarray | None:
    """When the slowest 1 % of the window's open-loop requests were DUE, ns;
    a failure is slower than any answer. None without such requests."""
    due, took = [], []
    for g in run.log.values():
        if isinstance(g, dict) and g.get("kind") == "open_loop":
            due.append(np.rint(np.asarray(g["due"]) * 1e9).astype(np.int64))
            took.append(np.where(g["ok"], np.asarray(g["done"]) - np.asarray(g["due"]), np.inf))
    if not due:
        return None
    due, took = np.concatenate(due), np.concatenate(took)
    k = max(1, due.shape[0] // 100)
    return due[np.argsort(-took, kind="stable")[:k]]


def due_inside(due_ns: np.ndarray, found) -> np.ndarray:
    """For each due time, whether it lies inside one of the holds (both
    ends included)."""
    inside = np.zeros(due_ns.shape[0], bool)
    for t0, t1, *_ in found:
        inside |= (due_ns >= t0) & (due_ns <= t1)
    return inside


def tail_due_in_hold_share(run) -> float | None:
    found, due = holds(run), slowest(run)
    if found is None or due is None:
        return None
    return float(due_inside(due, found).mean())


def turn_wait_ms(run) -> float | None:
    """``late_ns / ticks`` of the window, every loop that ticked over it
    together (the hold log does not say which loop a hold was on: a
    process's servers share one).

    A hold's tick is in the hold log with both its ends, so it counts with
    what of it lies inside the window (and as a tick where it was DUE there).
    The ticks that ran under ``HOLD_MIN_NS`` late are known between two
    roll-up rows only as a sum (the rows' difference less the holds' ticks
    that ran between them); where the window's edge falls between two rows
    that sum is shared out by the time the loop turned in (the stretch less
    its holds) on either side of the edge."""
    rows, found = tick_rows(run), every_hold(run)
    if found is None:
        return None
    union = plugin(run.bench, "layers", "_stages").union_ns
    lo, hi = window_ns(run)
    late = sum(b - a for a, b in clipped(found, lo, hi))
    ticks = sum(1 for h in found if lo <= h[0] < hi)

    def turned_ns(a: int, b: int) -> int:
        return (b - a) - union(clipped(found, a, b))

    for loop in rows.values():
        for before, after in zip(loop, loop[1:]):
            a, b = max(before[0], lo), min(after[0], hi)
            ran = [h for h in found if before[0] < h[1] <= after[0]]
            whole = turned_ns(before[0], after[0])
            share = turned_ns(a, b) / whole if whole > 0 else 0.0
            late += share * (after[2] - before[2] - sum(h[1] - h[0] for h in ran))
            ticks += share * (after[1] - before[1] - len(ran))
    return late / ticks / 1e6 if ticks > 0 else None


def bench_span(run, t0: int, t1: int) -> str | None:
    """Where the harness was: ``in <span>`` for its own span that covers
    most of ``t0..t1`` (the shortest of those that tie: a child before its
    container), if one covers half; else ``before <span>`` where one of its
    spans begins within 10 ms of the hold's end (what a generator does in a
    thread right before its span: a wave's 65,536 ids)."""
    best = nxt = None
    for name, a, b in run.spans:
        if not name.startswith("bench."):
            continue
        over = min(b, t1) - max(a, t0)
        if 2 * over >= t1 - t0:
            best = min(best or (-over, b - a, name), (-over, b - a, name))
        elif 0 <= a - t1 <= 10_000_000:
            nxt = min(nxt or (a, b - a, name), (a, b - a, name))
    return f"in {best[2]}" if best else f"before {nxt[2]}" if nxt else None


def note_table(run) -> None:
    """One table a run on standard error: per cause the holds of the window
    (what of each lies inside it), their total and longest, what of them no
    stage names, and how many of the slowest 1 % of requests were due inside
    them. A hold the program left unnamed is listed under the harness's span
    that covers it, where one does; else, where it began with the very next
    tick after another hold, as ``after a hold`` (the turns in which the loop
    works off what queued behind that one, or code that runs right after it:
    the log cannot tell, and the metrics count it as unnamed all the same)."""
    found = holds(run)
    if found is None:
        return
    lo, hi = window_ns(run)
    due = slowest(run)
    rows: dict = {}
    ended = None  # when the hold before this one ended
    for h in sorted(found):
        cause = h[2]
        if cause == UNNAMED:
            where = bench_span(run, h[0], h[1])
            if where is None and ended is not None and h[0] - ended <= TICK_NS:
                where = "after a hold"
            cause = f"{UNNAMED} {where}" if where else UNNAMED
        ended = h[1]
        inside = min(h[1], hi) - max(h[0], lo)
        row = rows.setdefault(cause, [0, 0, 0, 0, 0])
        row[0] += 1
        row[1] += inside
        row[2] = max(row[2], inside)
        row[3] += unnamed_ns(h, lo, hi)
        row[4] += 0 if due is None else int(due_inside(due, [h]).sum())
    slow = 0 if due is None else due.shape[0]
    note(f"loop holds in the window, by cause (slowest 1 % = {slow} requests; "
         f"turn wait {turn_wait_ms(run)} ms):")
    note(f"  {'cause':44s} {'holds':>6s} {'total_ms':>10s} {'max_ms':>9s} "
         f"{'unnamed_ms':>10s} {'slow_due':>8s}")
    by_total = sorted(rows.items(), key=lambda kv: -kv[1][1])
    for cause, (n, total, longest, bare, slow_due) in by_total:
        note(f"  {cause:44s} {n:6d} {total / 1e6:10.2f} {longest / 1e6:9.2f} "
             f"{bare / 1e6:10.2f} {slow_due:8d}")
    # The blind spot, split: the harness's own work (inside one of its spans,
    # or right before one), what a named hold's stages leave uncovered, and
    # holds with neither stage nor span.
    bare = {"in": 0, "before": 0, "after": 0, "named": 0, "none": 0}
    for cause, row in rows.items():
        words = cause.split()
        bare[words[1] if len(words) > 1 else "none" if cause == UNNAMED else "named"] += row[3]
    per_s = 1e6 * (run.window[1] - run.window[0])
    note(f"  unnamed {sum(bare.values()) / per_s:.2f} ms/s = in the harness's spans "
         f"{bare['in'] / per_s:.2f} + right before one {bare['before'] / per_s:.2f} + the next "
         f"tick after a hold {bare['after'] / per_s:.2f} + left over by the stages of named holds "
         f"{bare['named'] / per_s:.2f} + bare {bare['none'] / per_s:.2f}")
    # The two instruments side by side: the longest hold that began in the
    # window (as ``hold_max_ms`` has it) and the record of the stage that
    # names it.
    began = [h for h in found if lo <= h[0] < hi]
    if began:
        t0, t1, cause, _named = max(began, key=lambda h: min(h[1], hi) - h[0])
        recs = plugin(run.bench, "layers", "_stages").records(run) or ()
        over, took = max(
            ((min(r[2], t1) - max(r[1], t0), r[2] - r[1]) for r in recs
             if r[0] == cause.lstrip("~") and r[2] > t0 and r[1] < t1), default=(0, 0))
        note(f"  longest hold {(t1 - t0) / 1e6:.2f} ms at {(t0 - lo) / 1e9:.3f} s of the window, "
             f"{cause}: its stage record is {took / 1e6:.2f} ms, {over / 1e6:.2f} of them inside")
