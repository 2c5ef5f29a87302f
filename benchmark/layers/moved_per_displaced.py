"""Rows the daemons' committed solves moved in the window
(``PlacementDaemonStats.moves``: delta solves, and the full solve that the
staleness bound ``max_delta_solves`` makes of every ninth) over the least any
plan must move for the same events (``reference/churn.py``, summed by
``audits/churn_served.py``): 1.0 is exact; a derate stepping between events
re-plans seats nobody displaced. ``rio.place.delta.moved`` counts the delta
solves' share of it and is on the ``churn`` line."""


def read(run):
    least = run.log.get("churn.least_moves")
    if not least or "daemons1" not in run.log:
        return None
    moved = run.log["daemons1"].get("moves", 0) - run.log["daemons0"].get("moves", 0)
    return moved / least
