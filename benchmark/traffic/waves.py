"""Activation waves: every ``period_s`` seconds, ``wave_size`` new ids are
seated by one ``assign_batch`` call and read back through ``lookup_batch``.

The directory grows by ``wave_size`` per wave. The harness keeps the
per-node seat counts before and after every wave from its own reads.
"""

import asyncio
import time

import numpy as np


def _state(run, params) -> dict:
    key = "_waves." + params["name"]
    if key not in run.log:
        c = run.cluster
        n_waves = params["warm_waves"] + int(run.args.seconds / params["period_s"]) + 1
        start = len(c.names)
        size = params["wave_size"]
        names = [
            np.array([str(start + w * size + i) for i in range(size)], object)
            for w in range(n_waves)
        ]
        run.log[key] = {"names": names, "next": 0, "counts": run.cluster.counts(run.log["seats0"])}
    return run.log[key]


async def _wave(run, params, st: dict) -> dict:
    c = run.cluster
    names = st["names"][st["next"]]
    st["next"] += 1
    before = st["counts"]
    # The ids are the caller's to make, and are made off the loop.
    ids = await asyncio.to_thread(c.make_ids, names)
    with run.span("bench.wave"):
        t0 = time.perf_counter()
        with run.span("bench.wave.assign_batch"):
            addrs = await c.placement.assign_batch(ids)
        with run.span("bench.wave.lookup_batch"):
            seats = await c.seats(names)
        t1 = time.perf_counter()
    returned = np.fromiter((c.index_of[a] for a in addrs), np.int64, count=len(addrs))
    st["counts"] = before + np.bincount(seats[seats >= 0], minlength=before.shape[0])
    return {
        "t0": t0, "t1": t1, "n": len(ids), "before": before, "after": st["counts"],
        "unseated": int((seats < 0).sum()), "differ": int((seats != returned).sum()),
        "seats": seats,
    }


async def warm(run, params) -> None:
    st = await asyncio.to_thread(_state, run, params)
    st["warm"] = [await _wave(run, params, st) for _ in range(params["warm_waves"])]


async def drive(run, params, t_start: float, t_end: float) -> None:
    st = _state(run, params)
    waves = []
    t_next = t_start + params["first_s"]
    while t_next < t_end and st["next"] < len(st["names"]):
        await asyncio.sleep(max(t_next - time.perf_counter(), 0.0))
        waves.append(await _wave(run, params, st))
        t_next += params["period_s"]
    run.log[params["name"]] = {
        "kind": "waves", "waves": st.get("warm", []) + waves, "timed": len(waves),
        "counts": st["counts"], "names": np.concatenate(st["names"][: st["next"]]),
    }
