"""What the four-chip cell's readers share: the re-plan generator's record
and the stage log's records of the window's mesh solves (the calls that hold
a ``solve.mesh.cells`` record). Everything returns ``None`` where the program
keeps no such record (a commit from before the stages), never raises for
that."""

import statistics

from benchmark.harness import plugin

PROGRAM = "mesh_cell_solve"  # the cell program's name in the device trace


def replans(run) -> list:
    """The window's re-plans, as ``traffic/full_resolve.py`` recorded them."""
    log = next(
        (g for g in run.log.values() if isinstance(g, dict) and g.get("kind") == "full_resolve"),
        None,
    )
    return [] if log is None else log["replans"]


def mesh_calls_ms(run, names) -> float | None:
    """Median, over the window's solves that were sharded over a mesh, of
    the time the named stages took in the call."""
    recs = plugin(run.bench, "layers", "_stages").records(run) or ()
    calls = {r[4] for r in plugin(run.bench, "layers", "_churn").in_window(run, "solve.mesh.cells")}
    took = [
        sum(r[2] - r[1] for r in recs if r[4] == call and r[0] in names) / 1e6
        for call in calls
    ]
    return statistics.median(took) if took else None
