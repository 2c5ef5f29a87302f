"""Open-loop requests that follow the directory: ``open_loop.py``'s twin for
a cluster whose members come and go while the requests arrive.

``open_loop.py`` counts an answer from any server outside this host's live
ones as a failure. Under churn a request that races a hand-off is rightly
answered by the member its object moved to, so here an answer is ``ok`` when
it comes from an ACTIVE member that the directory named for the object when
the request was sent or when it was answered (the rule of
``audits/routed.py``); anything else, a timeout too, is a failure. In all
else it is ``open_loop.py``: a fixed number of arrivals uniform over the
window, each timed from when it was DUE, sent to ids the directory seats on
a live server (one seated elsewhere when its turn comes is redrawn), nothing
kept per finished request. The record goes under the same keys with
``"kind": "open_loop"``, which is what the readers select by.

Parameters: ``name``, ``op``, ``rate_per_s`` (``run.py --rate-per-s``
overrides it: the rate sweep).
"""

import asyncio
import time

import numpy as np

from benchmark.harness import plugin, run_sync


def _base(run):
    return plugin(run.bench, "traffic", "open_loop")


async def warm(run, params) -> None:
    await _base(run).warm(run, params)


async def drive(run, params, t_start: float, t_end: float) -> None:
    c = run.cluster
    op = getattr(run.app, params["op"])
    seconds = t_end - t_start
    rate = getattr(run.args, "rate_per_s", None) or params["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    rng = run.rng(params["name"])
    due = t_start + np.sort(rng.random(n)) * seconds
    pool = _base(run)._pool(run)
    picks = rng.integers(0, pool.shape[0], size=n)
    spare = rng.integers(0, pool.shape[0], size=4 * n)
    live = set(c.live)
    timeout = c.request_timeout
    sent = np.zeros(n)
    done = np.zeros(n)
    ok = np.zeros(n, bool)
    counts = {"redrawn": 0, "from_inactive": 0, "from_unnamed": 0, "moved_in_flight": 0}
    placement, names = c.placement, c.names
    async def one(k: int, name: str, named_at_send: str) -> None:
        sent[k] = time.perf_counter()
        try:
            async with asyncio.timeout(timeout):
                server = await op(c.client, name)
            named_now = run_sync(placement.lookup(c.oid(name)))
            # This host's servers never leave; any other member is looked
            # up (a read copies the table, and such answers are few).
            active = server in live or run_sync(c.members.is_active(server))
            named = server in (named_at_send, named_now)
            counts["from_inactive"] += not active
            counts["from_unnamed"] += not named
            counts["moved_in_flight"] += server != named_at_send
            ok[k] = active and named
        except Exception:  # noqa: BLE001 - counted: a failure is beyond any percentile
            ok[k] = False
        done[k] = time.perf_counter()

    # Only the requests in flight are held (open_loop.py says why).
    pending: set = set()
    k = s = 0
    while k < n:
        now = time.perf_counter()
        while k < n and due[k] <= now:
            i = int(pool[picks[k]])
            while (named := run_sync(placement.lookup(c.oid(names[i])))) not in live:
                i = int(pool[spare[s % spare.shape[0]]])
                s += 1
                counts["redrawn"] += 1
            task = asyncio.create_task(one(k, names[i], named))
            pending.add(task)
            task.add_done_callback(pending.discard)
            k += 1
        if k < n:
            await asyncio.sleep(min(max(due[k] - time.perf_counter(), 0.0), 0.002))
    if pending:
        await asyncio.gather(*pending)
    run.log[params["name"]] = {
        "kind": "open_loop", "due": due, "sent": sent, "done": done, "ok": ok,
        "timeout_s": timeout, "follows_directory": True, **counts,
    }
