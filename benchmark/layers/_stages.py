"""What the readers of the program's own records share: the stage log
(``rio_tpu.tracing.stage_log``: coarse host stages on ``perf_counter_ns``,
the clock ``run.window`` and ``run.spans`` are on) and the load monitors'
per-tick loop-lag samples.

A record is ``(name, t0_ns, t1_ns, parent, call_id, thread_id)``. Everything
here selects by timestamp and returns ``None`` where the program keeps no
such record (a commit from before the stage log), never raises for that.
"""

import math

# Stages that time a wait (the loop runs other work meanwhile), not a hold.
WAITS = ("place.lock_wait", "place.resume")
FULL_COLLECTION = "gc.gen2"


def records(run):
    """The process's stage log, oldest first; None where the program has none."""
    from rio_tpu import tracing

    log = getattr(tracing, "stage_log", None)
    return None if log is None else log()


def window_ns(run) -> tuple:
    return int(run.window[0] * 1e9), int(run.window[1] * 1e9)


def wave_spans(run) -> list:
    """``(t0_ns, t1_ns)`` of the harness's ``bench.wave`` spans that began
    inside the window (set-up's warm-up waves are left out)."""
    lo, hi = window_ns(run)
    return [(a, b) for name, a, b in run.spans if name == "bench.wave" and lo <= a < hi]


def union_ns(intervals) -> int:
    total, end = 0, -math.inf
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def clipped(recs, lo: int, hi: int) -> list:
    return [(max(r[1], lo), min(r[2], hi)) for r in recs if r[2] > lo and r[1] < hi]


def per_wave_ms(run, names) -> float | None:
    """Mean over the window's waves of the time the named stages took inside
    the wave; None without waves or without one such record inside them."""
    recs, waves = records(run), wave_spans(run)
    if not recs or not waves:
        return None
    inside = [
        r[2] - r[1] for a, b in waves for r in recs
        if r[0] in names and r[1] >= a and r[2] <= b
    ]
    return sum(inside) / len(waves) / 1e6 if inside else None


def leaves(recs) -> list:
    """Records no other record of the same call names as its parent: the
    stages that tile their call (a container's time is its children's)."""
    containers = {(r[4], r[3]) for r in recs if r[3] is not None}
    return [r for r in recs if (r[4], r[0]) not in containers]


def unattributed_ms(waves, recs) -> float | None:
    """Mean over ``waves`` of the wave's span minus the union of every leaf
    stage (full collections among them) inside it."""
    if not waves or not recs:
        return None
    tiles = leaves(recs)
    if not any(r[2] > a and r[1] < b for a, b in waves for r in tiles):
        return None
    bare = sum((b - a) - union_ns(clipped(tiles, a, b)) for a, b in waves)
    return bare / len(waves) / 1e6


def loop_held_ms(waves, recs) -> float | None:
    """Mean over ``waves`` of the stages' self time on the event-loop thread
    (the thread of the wave's ``place.assign``): a stage's duration minus
    what its child stages cover, waits left out."""
    if not waves or not recs:
        return None
    held, seen = 0, False
    for a, b in waves:
        inside = [r for r in recs if r[1] >= a and r[2] <= b]
        loop = next((r[5] for r in inside if r[0] == "place.assign"), None)
        if loop is None:
            continue
        seen = True
        for r in inside:
            if r[5] != loop or r[0] in WAITS or r[0] == FULL_COLLECTION:
                continue
            kids = [c for c in inside if c[3] == r[0] and c[4] == r[4] and c is not r]
            held += (r[2] - r[1]) - union_ns(clipped(kids, r[1], r[2]))
    return held / len(waves) / 1e6 if seen else None


def full_collections(run) -> list | None:
    """The records of every full collection in the log; None where the
    program logs none."""
    found = [r for r in records(run) or () if r[0] == FULL_COLLECTION]
    return found or None


def gc_full_ms_per_s(run) -> float | None:
    found = full_collections(run)
    if found is None:
        return None
    inside = union_ns(clipped(found, *window_ns(run)))
    return inside / 1e6 / (run.window[1] - run.window[0])


def lag_samples(run) -> list | None:
    """``(t_ns, lag_ms)`` of every live server's load monitor for the ticks
    DUE inside the window: the tick due at ``t - lag`` ran at ``t``. (A tick
    due before the window that ran inside it was late on the harness's own
    set-up: its last warm-up wave and its forced collection.)"""
    lo, hi = window_ns(run)
    out, found = [], False
    for s in getattr(run.cluster, "servers", ()):
        samples = getattr(getattr(getattr(s, "load_monitor", None), "stats", None),
                          "lag_samples", None)
        if samples is None:
            continue
        found = True
        out += [(t, ms) for t, ms in list(samples) if lo <= t - int(ms * 1e6) and t <= hi]
    return out if found and out else None


def uncollected_lags(run) -> list | None:
    """Each in-window lag sample in ms, minus what ``gc.gen2`` stages cover of
    the interval the loop was late in: how late a tick ran for a reason other
    than a full collection (those have ``gc_full_ms_per_s``)."""
    samples = lag_samples(run)
    if samples is None:
        return None
    full = full_collections(run) or []
    return [
        lag_ms - union_ns(clipped(full, t - int(lag_ms * 1e6), t)) / 1e6
        for t, lag_ms in samples
    ]


def setup_stage_s(run, name: str) -> float | None:
    """Seconds the named stage took in calls that ended before the window."""
    recs = records(run)
    if not recs:
        return None
    lo = window_ns(run)[0]
    took = [r[2] - r[1] for r in recs if r[0] == name and r[2] <= lo]
    return sum(took) / 1e9 if took else None
