"""Seeded users seated on live servers get one request each: every answer
comes from the server ``lookup`` names, with no redirect."""

import asyncio

import numpy as np

from benchmark.harness import run_sync


async def audit(run, phase: str) -> None:
    c = run.cluster
    seats = run.log["seats.last"]
    pool = np.nonzero(np.isin(seats, c.live_idx))[0]
    rng = run.rng("routed")
    n = min(run.config.get("routed_requests", 1024), pool.shape[0])
    picks = rng.choice(pool, size=n, replace=False).tolist()
    op = getattr(run.app, run.config["routed_op"])
    redirects0 = c.client.stats.redirects
    wrong = failed = 0
    for i in picks:
        oid = c.oid(c.names[i])
        before = run_sync(c.placement.lookup(oid))
        try:
            async with asyncio.timeout(c.request_timeout):
                server = await op(c.client, oid.id)
        except Exception:  # noqa: BLE001 - counted
            failed += 1
            continue
        # The directory is read on both sides of the request, so that a
        # move between them cannot be taken for a misroute.
        wrong += server not in (before, run_sync(c.placement.lookup(oid)))
    run.check(f"{phase}.routed_failed", failed, 0)
    run.check(f"{phase}.routed_to_another_server", wrong, 0)
    run.check(f"{phase}.routed_redirects", c.client.stats.redirects - redirects0, 0)
