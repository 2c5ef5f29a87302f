"""Every acknowledged sample is in the SQLite row read back after the
window: the stored ``(count, total, vmin, vmax)`` of every actor touched
equals the dict replay of the acknowledged requests, exactly."""

import asyncio
import json
import sqlite3

from benchmark.reference import aggregate


def _read(path: str, kind: str, state_type: str) -> dict:
    with sqlite3.connect(f"file:{path}?mode=ro", uri=True) as db:
        rows = db.execute(
            "SELECT object_id, serialized_state FROM state_provider_object_state "
            "WHERE object_kind=? AND state_type=?", (kind, state_type),
        ).fetchall()
    out = {}
    for oid, raw in rows:
        s = json.loads(raw)
        out[oid] = (int(s["count"]), float(s["total"]), float(s["vmin"]), float(s["vmax"]))
    return out


async def audit(run, phase: str) -> None:
    c = run.cluster
    stored = await asyncio.to_thread(_read, c.state_path, run.app.TYPE, run.app.STATE_TYPE)
    acked, failed = list(run.log.get("acked_in_setup", [])), []
    for g in run.log.values():
        if isinstance(g, dict) and g.get("kind") == "closed_loop":
            acked += g["acked"]
            failed += g["failed"]
    want = aggregate.replay(acked)
    run.check(f"{phase}.acked_samples", len(acked), 0, ok=len(acked) > 0)
    run.check(f"{phase}.actors_touched", len(want), 0, ok=len(want) > 0)
    run.check(f"{phase}.actors_differ_from_replay", aggregate.mismatches(acked, failed, stored), 0)
    run.check(
        f"{phase}.stored_actors_nobody_sent_to",
        len(set(stored) - set(want) - {a for n, t, _ in failed for a in (n, f"{n}.{t}")}), 0,
    )
