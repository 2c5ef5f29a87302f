"""Closed-loop drivers: each sends its next request when the last one is
acknowledged, for the whole window.

Parameters: ``name``, ``op`` (``op(client, name, tag, value)``),
``drivers``, ``values`` (values are the integers below it, as floats).
Names and tags are uniform over the configuration's own.
"""

import asyncio
import time

import numpy as np


async def warm(run, params) -> None:
    c = run.cluster
    op = getattr(run.app, params["op"])
    # A request per driver opens the connections. They are acknowledged
    # samples like the window's, and the audit replays them with those.
    reqs = [(f"m{d % run.config['metric_names']}", "tag0", 1.0) for d in range(params["drivers"])]
    await asyncio.gather(*(op(c.client, *r) for r in reqs))
    run.log.setdefault("acked_in_setup", []).extend(reqs)


async def drive(run, params, t_start: float, t_end: float) -> None:
    c = run.cluster
    op = getattr(run.app, params["op"])
    n_names, n_tags = run.config["metric_names"], run.config["tags"]
    timeout = c.request_timeout
    acked: list = []
    failed: list = []
    lat: list = []

    async def driver(d: int) -> None:
        rng = run.rng(f"{params['name']}.{d}")
        while True:
            names = rng.integers(0, n_names, size=1024).tolist()
            tags = rng.integers(0, n_tags, size=1024).tolist()
            values = rng.integers(0, params["values"], size=1024).tolist()
            for a, b, v in zip(names, tags, values):
                t0 = time.perf_counter()
                if t0 >= t_end:
                    return
                req = (f"m{a}", f"tag{b}", float(v))
                try:
                    async with asyncio.timeout(timeout):
                        await op(c.client, *req)
                    acked.append(req)
                    lat.append((t0, time.perf_counter(), True))
                except Exception:  # noqa: BLE001 - counted as failed
                    failed.append(req)
                    lat.append((t0, time.perf_counter(), False))

    await asyncio.gather(*(driver(d) for d in range(params["drivers"])))
    arr = np.array(lat, np.float64).reshape(-1, 3)
    run.log[params["name"]] = {
        "kind": "closed_loop", "due": arr[:, 0], "sent": arr[:, 0], "done": arr[:, 1],
        "ok": arr[:, 2] > 0, "acked": acked, "failed": failed, "timeout_s": timeout,
    }
