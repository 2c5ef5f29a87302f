"""The churn was served, event by event, and the cluster is whole at rest.

Limits, each with its reason:

* ``churn.events_unserved`` 0, ``churn.slowest_event_ms`` <= the mix's
  ``serve_limit_s`` (8 s: two periods): an event not served before the next
  but one begins means events arrive faster than they are served, and the
  rows on dead nodes pile up for as long as the churn lasts. The time is the
  time the PROCESS ran between flip and served: a stop of the whole process
  by the machine (the watcher's own thread woke seconds late,
  ``traffic/churn.py``) served nothing and brought no event either, so it is
  taken out, and printed on the ``churn`` line beside the raw times. A loop
  held or saturated by the program is not a stop, and counts in full.
* ``churn.rows_on_inactive`` 0: served means no row is left behind.
* (``quotas_exact.py``, listed beside this audit, holds the daemons' own
  result at quiescence to the exact largest-remainder shares.)
* ``churn.events_moved_too_few`` 0: at the first flip after an event was
  served, its leavers have lost every row they held at its flip; fewer means
  rows were left behind, or put back on a node that had left by a plan made
  before it left. That is the part of the reference's least
  (``reference/churn.py``) that two readings of the counts can prove. The
  whole of it (the survivors' excess and the rejoiners' shortfall under
  the capacities the solve was given) cannot be held against a later reading:
  a monitor may step a live server's price between the two, and the later
  one may fall into a plan's hand-offs. ``churn.least_moves``, what
  ``moved_per_displaced`` divides by, is that whole, under capacities
  inferred from the later reading.
* ``churn.objects_active_twice`` 0 and ``churn.activations_off_seat`` 0: the
  registries of every server of the process are read; an object is live on
  one node at most, the one the directory names (a hand-off that skipped the
  source's deactivate leaves it live where the directory no longer points).
* ``churn.answers_from_inactive`` 0: no heartbeat was acknowledged by a
  member that had left.
* ``churn.open_descriptors`` under 8,000 at the window's end and now: one
  process holds the whole cluster's hand-off mesh, and PR 23's attempt ended
  at 17,413 of the 20,000 a process may open.
"""

import asyncio

import numpy as np

from benchmark.harness import emit, plugin
from benchmark.reference import churn as reference
from benchmark.reference import quotas

DESCRIPTOR_LIMIT = 8000


def ran_between(t0: float, t1: float, stops) -> float:
    """Seconds of ``t0 .. t1`` in which the process ran: what is left when
    the witnessed stops ``(from, to)`` are taken out."""
    return (t1 - t0) - sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in stops)


def _registries(run, members) -> list:
    servers = list(run.cluster.servers) + [s for s, _ in members.servers.values()]
    return [(s.local_address, s.registry) for s in servers]


async def audit(run, phase: str) -> None:
    c = run.cluster
    log = next(
        (g for g in run.log.values() if isinstance(g, dict) and g.get("kind") == "churn"), None
    )
    if log is None:
        run.failures.append("churn_served: no churn generator ran")
        return
    events = [ev for ev in log["events"] if ev["in_window"]]
    served = [ev for ev in events if ev["t_served"] == ev["t_served"]]
    served_ms = [(ev["t_served"] - ev["t_flip"]) * 1e3 for ev in served]
    stops = log.get("process_stops", [])
    ran_ms = [ran_between(ev["t_flip"], ev["t_served"], stops) * 1e3 for ev in served]
    limit_ms = log["serve_limit_s"] * 1e3
    run.check("churn.events_unserved", len(events) - len(served_ms), 0)
    run.check("churn.slowest_event_ms", max(ran_ms, default=0.0), limit_ms)

    seats = run.log["seats.last"]
    active = await c.active_mask()
    counts = c.counts(seats)
    run.check("churn.rows_on_inactive", int(counts[~active].sum()), 0)

    # Per event: from the counts at its flip to the counts at the first flip
    # after it was served (at rest for the last ones), against the least any
    # plan must move. An event's leavers stay away for three events, so a
    # later reading cannot hide rows that never left them.
    too_few, least_total = 0, 0
    for k, ev in enumerate(events):
        later = [e for e in events[k + 1:] if e["t_flip"] >= ev["t_served"]]
        after = later[0]["before"] if later else counts
        cap_ev = np.where(ev["active"], 1.0, 0.0)
        cap_ev[c.live_idx] = quotas.infer_capacity(after, ev["active"], c.live_idx)[c.live_idx]
        least_total += reference.event(ev["before"], cap_ev, ev["leavers"])["least_moves"]
        gone = ev["leavers"]
        too_few += int(
            reference.moved_at_least(ev["before"][gone], after[gone]) < int(ev["before"][gone].sum())
        )
    run.log["churn.least_moves"] = least_total
    run.check("churn.events_moved_too_few", too_few, 0)
    gauges = {
        k: log["gauges1"][k] - log["gauges0"].get(k, 0.0)
        for k in sorted(log["gauges1"]) if k.startswith(("rio.place.delta", "rio.load"))
    }
    # The program's own record of its last solves and of the plans actuated,
    # printed for whoever reads the run; no check reads them.
    stats = getattr(c.placement, "stats", None)
    solves = [
        [x.mode, x.moved, x.displaced, bool(x.discarded), round(x.solve_ms), round(x.apply_ms)]
        for x in [*getattr(stats, "history", []), stats] if x is not None
    ][-16:]
    plans: dict = {}
    for s in c.servers:
        for k, v in vars(s.migration_manager.stats).items():
            if k.startswith("plan") or k == "aborted":
                plans[k] = max(plans.get(k, 0), v) if k.endswith("_max") else plans.get(k, 0) + v
    emit({"churn": {
        "served_ms": [round(ms, 1) for ms in served_ms], "least_moves": least_total,
        "process_stops_ms": [round((b - a) * 1e3, 1) for a, b in stops],
        "ran_ms": [round(ms, 1) for ms in ran_ms] if stops else "= served_ms",
        "descriptors": [ev["descriptors"] for ev in events], "counters_in_window": gauges,
        "solves": solves, "plans": plans,
    }})

    # One live activation per object, where the directory says.
    tname = c.tname
    held: dict = {}
    off_seat = 0
    for address, registry in _registries(run, log["members"]):
        mine = [o.id for o in registry.object_ids() if o.type_name == tname]
        where = await asyncio.to_thread(
            c.seats_sync, np.array(mine, object)
        ) if mine else np.zeros(0, np.int64)
        strays = np.flatnonzero(where != c.index_of[address])
        off_seat += int(strays.shape[0])
        for k in strays[:3].tolist():  # printed for whoever reads the run
            emit({"off_seat": {"object": mine[k], "live_on": address,
                               "directory": c.node_order[int(where[k])] if where[k] >= 0 else None,
                               "holder_is_live_server": address in c.live}})
        for oid in mine:
            held[oid] = held.get(oid, 0) + 1
    run.check("churn.objects_active_twice", sum(1 for n in held.values() if n > 1), 0)
    run.check("churn.activations_off_seat", off_seat, 0)

    beats = [
        g for g in run.log.values()
        if isinstance(g, dict) and g.get("kind") == "open_loop" and "from_inactive" in g
    ]
    run.check("churn.answers_from_inactive", sum(g["from_inactive"] for g in beats), 0)
    now = plugin(run.bench, "traffic", "churn").open_descriptors()
    run.check(
        "churn.open_descriptors", max(log["descriptors_at_end"], now), DESCRIPTOR_LIMIT - 1
    )
