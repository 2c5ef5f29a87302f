"""What the churn cell's readers share: the churn generator's record, the
stage log's records inside the window, and the calls that ran as a delta
solve. Everything returns ``None`` where the program keeps no such record
(a commit from before the stage or the counter), never raises for that."""

import statistics

from benchmark.harness import plugin


def record(run):
    """The churn generator's log, or None where none ran."""
    return next(
        (g for g in run.log.values() if isinstance(g, dict) and g.get("kind") == "churn"), None
    )


def events(run) -> list:
    log = record(run)
    return [] if log is None else [ev for ev in log["events"] if ev["in_window"]]


def in_window(run, name: str) -> list:
    """The stage log's records of ``name`` that began inside the window."""
    st = plugin(run.bench, "layers", "_stages")
    lo, hi = st.window_ns(run)
    return [r for r in st.records(run) or () if r[0] == name and lo <= r[1] < hi]


def median_ms(recs) -> float | None:
    return statistics.median((r[2] - r[1]) / 1e6 for r in recs) if recs else None


def delta_calls_ms(run, names) -> float | None:
    """Median, over the window's solves that ran as a delta (their call
    holds a ``solve.delta`` record), of the time the named stages took."""
    st = plugin(run.bench, "layers", "_stages")
    recs = st.records(run) or ()
    calls = {r[4] for r in in_window(run, "solve.delta")}
    took = [
        sum(r[2] - r[1] for r in recs if r[4] == call and r[0] in names) / 1e6
        for call in calls
    ]
    return statistics.median(took) if took else None


def gauge_delta(run, key: str) -> float | None:
    """A counter of ``place_gauges`` over the window; None where the program
    has no such counter."""
    log = record(run)
    if log is None or key not in log["gauges1"]:
        return None
    return log["gauges1"][key] - log["gauges0"].get(key, 0.0)
