"""Full re-plans of the whole directory at a fixed period, made as a
placement daemon makes them: ``rebalance(delta=False)`` with a live server's
``MigrationManager.apply_moves`` as the ``move_sink``
(``PlacementDaemon._rebalance``), so a row that moves is handed off by its
source and "one live activation" holds. Never a raw directory write.

``warm()`` stands a ``Server`` of the program behind every active member row
(``traffic/churn.py``'s ``Members``: a re-plan hands rows to members no
heartbeat ever dials, and a member nobody listens for fails the hand-off
after 20 retries), waits for the derates set-up's own holds of the loop drew
to come to rest, and makes one re-plan outside the window: the seeded solve,
the hand-offs' connections.

Per re-plan the generator stamps the call, the commit (the sink is called
right after the plan is committed; without moves, the call's return) and the
end of the hand-offs, and records ``SolveStats.mode`` / ``devices`` /
``chunks`` / ``moved``. A solve that lost an epoch race is made again, as
``harness.full_solve`` does.

Parameters: ``name``, ``period_s``, ``first_s``.
"""

import asyncio
import time

import numpy as np

from benchmark.harness import emit, note, plugin

KEY = "_resolve"


def _coarse_g(placement):
    """The coarse potentials of the directory's committed plan, copied: the
    plan record, read here and nowhere else (audits/resolve_plan.py compares
    them with the reference's). None before a two-level solve committed."""
    plan = getattr(placement, "_plan", None)
    return None if plan is None or plan.coarse_g is None else np.array(plan.coarse_g)


async def replan(run, span_name: str, in_window: bool) -> dict:
    """One committed full re-plan; its record is appended to the log."""
    c = run.cluster
    apply_moves = c.servers[0].migration_manager.apply_moves
    rec = {"in_window": in_window, "t_call": time.perf_counter(), "t_commit": None, "attempts": 0,
           "seed": _coarse_g(c.placement)}  # what the solve is seeded with

    async def sink(moves):
        rec["t_commit"] = time.perf_counter()
        return await apply_moves(moves)

    for _ in range(5):
        rec["attempts"] += 1
        with run.span(span_name):
            moved = await c.placement.rebalance(delta=False, move_sink=sink)
        st = c.placement.stats
        if not st.discarded:
            break
        note(f"{span_name}: attempt {rec['attempts']} lost an epoch race, retrying")
        rec["t_call"] = time.perf_counter()
    else:
        run.failures.append(f"{span_name}: 5 solves in a row were discarded")
    rec["t_done"] = time.perf_counter()
    if rec["t_commit"] is None:
        rec["t_commit"] = rec["t_done"]
    rec.update(
        mode=st.mode, devices=st.devices, chunks=st.chunks, moved=int(moved),
        solve_ms=st.solve_ms, apply_ms=st.apply_ms, compile_ms=st.compile_ms,
        chunk_ms=list(st.chunk_ms), coarse_g=_coarse_g(c.placement),
    )
    want = run.config.get("solve_mode", {}).get("cpu" if run.rehearsal else "tpu")
    if want and st.mode != want:
        run.failures.append(f"{span_name} ran as {st.mode!r}, the configuration says {want!r}")
    run.log.setdefault(KEY, []).append(rec)
    emit({"replan": {
        "in_window": in_window, "mode": st.mode, "devices": st.devices, "chunks": st.chunks,
        "moved": rec["moved"], "attempts": rec["attempts"],
        "call_to_commit_ms": (rec["t_commit"] - rec["t_call"]) * 1e3,
        "handoffs_ms": (rec["t_done"] - rec["t_commit"]) * 1e3,
        "solve_ms": st.solve_ms, "compile_ms": st.compile_ms, "apply_ms": st.apply_ms,
        "chunk_ms": rec["chunk_ms"],
    }})
    return rec


async def warm(run, params) -> None:
    churn = plugin(run.bench, "traffic", "churn")
    members = churn.Members(run)
    active = await run.cluster.active_mask()
    t0 = time.perf_counter()
    with run.span("bench.resolve.bind_members"):
        for i in members.idx:
            if active[i]:
                members.start(i, await members.bind(i))
    note(f"{len(members.servers)} members bound in {time.perf_counter() - t0:.1f} s")
    run.log[KEY + ".members"] = members
    await churn._derates_at_rest(run)
    await replan(run, "bench.warm.replan", in_window=False)
    # The window opens on a directory at rest, as the churn cell's does.
    await churn._derates_at_rest(run, quiet_s=2.0, limit_s=10.0)


async def drive(run, params, t_start: float, t_end: float) -> None:
    gauges = getattr(run.cluster.placement, "place_gauges", None)
    gauges0 = dict(gauges()) if gauges is not None else {}
    k = 0
    while (t_next := t_start + params["first_s"] + k * params["period_s"]) < t_end:
        await asyncio.sleep(max(t_next - time.perf_counter(), 0.0))
        await replan(run, "bench.replan", in_window=True)
        k += 1
    run.log[params["name"]] = {
        "kind": "full_resolve",
        "replans": [r for r in run.log.get(KEY, []) if r["in_window"]],
        "gauges0": gauges0,
        "gauges1": dict(gauges()) if gauges is not None else {},
    }
