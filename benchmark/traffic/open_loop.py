"""Open-loop requests: a fixed number of arrivals, uniform over the window
(a Poisson process given its count), each timed from when it was DUE.

Parameters: ``name``, ``op`` (a coroutine of the app: ``op(client, id)``
returning the address of the server that answered), ``rate_per_s``. The
requests go to ids the directory seats on a live server; one seated
elsewhere when its turn comes is redrawn and not counted.
"""

import asyncio
import time

import numpy as np

from benchmark.harness import run_sync


def _pool(run) -> np.ndarray:
    """Indices (into ``cluster.names``) of the ids seated on live servers."""
    seats = run.log["seats0"]
    return np.nonzero(np.isin(seats, run.cluster.live_idx))[0]


async def warm(run, params) -> None:
    """Open the client's connections (bursts, so that the pool grows to
    what the window's concurrency asks for) and activate some actors."""
    c = run.cluster
    op = getattr(run.app, params["op"])
    pool = _pool(run)
    rng = run.rng(params["name"] + ".warm")
    for _ in range(4):
        picks = rng.choice(pool, size=min(256, pool.shape[0]), replace=False).tolist()
        await asyncio.gather(*(op(c.client, c.names[i]) for i in picks))


async def drive(run, params, t_start: float, t_end: float) -> None:
    c = run.cluster
    op = getattr(run.app, params["op"])
    seconds = t_end - t_start
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    rng = run.rng(params["name"])
    due = t_start + np.sort(rng.random(n)) * seconds
    pool = _pool(run)
    picks = rng.integers(0, pool.shape[0], size=n)
    spare = rng.integers(0, pool.shape[0], size=4 * n)
    live = set(c.live)
    timeout = c.request_timeout
    sent = np.zeros(n)
    done = np.zeros(n)
    ok = np.zeros(n, bool)
    redrawn = 0
    placement = c.placement
    names = c.names

    async def one(k: int, name: str) -> None:
        sent[k] = time.perf_counter()
        try:
            async with asyncio.timeout(timeout):
                server = await op(c.client, name)
            # An answer from a server that is not live is a failure.
            ok[k] = server in live
        except Exception:  # noqa: BLE001 - counted: a failure is beyond any percentile
            ok[k] = False
        done[k] = time.perf_counter()

    # Only the requests in flight are held: a window's worth of finished
    # tasks would be half a million objects for every full collection of the
    # interpreter's garbage to walk, charged to the servers on this loop.
    pending: set = set()
    k = s = 0
    while k < n:
        now = time.perf_counter()
        while k < n and due[k] <= now:
            i = int(pool[picks[k]])
            while run_sync(placement.lookup(c.oid(names[i]))) not in live:
                i = int(pool[spare[s % spare.shape[0]]])
                s += 1
                redrawn += 1
            task = asyncio.create_task(one(k, names[i]))
            pending.add(task)
            task.add_done_callback(pending.discard)
            k += 1
        if k < n:
            await asyncio.sleep(min(max(due[k] - time.perf_counter(), 0.0), 0.002))
    if pending:
        await asyncio.gather(*pending)
    run.log[params["name"]] = {
        "kind": "open_loop", "due": due, "sent": sent, "done": done, "ok": ok,
        "redrawn": redrawn, "timeout_s": timeout,
    }
