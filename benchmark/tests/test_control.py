"""The controls of `correct`, at a size a test run can hold.

* the solves in bfloat16 (``lowprec``) miss the quota audit and the
  waterfill comparison, which the float32 program passes;
* a run whose timed path is broken underneath comes out ``correct: false``.
"""

import asyncio
import json

import numpy as np
import pytest

from benchmark import lowprec
from benchmark import run as runner
from benchmark.audits import waves_balance
from benchmark.reference import quotas, waterfill


def _directory(n_objects, n_nodes, down):
    from rio_tpu import ObjectId
    from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

    async def build():
        p = JaxObjectPlacement(mode="sinkhorn")
        nodes = [f"10.0.0.{i}:7000" for i in range(n_nodes)]
        p.sync_members(nodes)
        ids = [ObjectId("T", str(i)) for i in range(n_objects)]
        await p.assign_batch(ids)

        class M:
            def __init__(self, a, active):
                self.address, self.active = a, active

        p.sync_members([M(a, i >= down) for i, a in enumerate(nodes)])
        await p.rebalance(delta=False)
        addrs = await p.lookup_batch(ids)
        idx = {a: i for i, a in enumerate(nodes)}
        counts = np.bincount([idx[a] for a in addrs], minlength=n_nodes)
        before = counts.copy()
        wave = [ObjectId("T", f"w{i}") for i in range(4096)]
        got = await p.assign_batch(wave)
        after = before + np.bincount([idx[a] for a in got], minlength=n_nodes)
        return counts, before, after

    return asyncio.run(build())


@pytest.fixture
def restore_solves():
    from importlib import import_module

    mods = [import_module(m) for m in (
        "rio_tpu.ops", "rio_tpu.object_placement.jax_placement", "rio_tpu.ops.assignment",
        "rio_tpu.ops.scaling", "rio_tpu.ops.sinkhorn", "rio_tpu.ops.structured",
    )]
    saved = [dict(vars(m)) for m in mods]
    yield
    for m, d in zip(mods, saved):
        for k, v in d.items():
            if getattr(m, k, None) is not v:
                setattr(m, k, v)


def test_bfloat16_solves_fail_the_audits_that_float32_passes(restore_solves):
    n, m, down = 70_000, 64, 3
    active = np.arange(m) >= down
    cap = active.astype(np.float64)
    full = np.nonzero(active)[0]
    counts, before, after = _directory(n, m, down)
    assert quotas.miss(counts, cap) == 0
    assert waterfill.full_member_deviation(before, after, cap, full) < waves_balance.DEVIATION_LIMIT_SEATS
    wrapped = lowprec.install("bfloat16")
    assert "rio_tpu.ops.sinkhorn.exact_quota_repair" in wrapped
    counts, before, after = _directory(n, m, down)
    assert quotas.miss(counts, cap) > 0
    assert waterfill.full_member_deviation(before, after, cap, full) > waves_balance.DEVIATION_LIMIT_SEATS


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_dropped_sample_makes_the_run_incorrect(capsys, monkeypatch):
    args = ["--workload", "metric-aggregator-100k-8.loadall", "--seed", "5", "--seconds", "2",
            "--rehearse-on-cpu"]
    assert runner.main(args) == 0
    assert _last_line(capsys)["correct"] is True
    # The answer altered where it is produced: one tag's aggregator
    # acknowledges a sample and saves nothing.
    from rio_tpu.state import sqlite as sqlite_state

    real_save = sqlite_state.SqliteState.save

    async def lossy(self, kind, oid, state_type, value):
        if oid.endswith(".tag1"):
            return None
        return await real_save(self, kind, oid, state_type, value)

    monkeypatch.setattr(sqlite_state.SqliteState, "save", lossy)
    assert runner.main(args) == 0
    line = _last_line(capsys)
    assert line["correct"] is False and line["attempted"] > 0


def test_a_wave_seated_off_its_plan_makes_the_run_incorrect(capsys, monkeypatch):
    from rio_tpu.object_placement.jax_placement import JaxObjectPlacement

    args = ["--workload", "presence-1m-1k.deploy-wave", "--seed", "6", "--seconds", "3",
            "--rehearse-on-cpu"]
    assert runner.main(args) == 0
    assert _last_line(capsys)["correct"] is True
    real = JaxObjectPlacement._apply_chunk
    seen = {"chunks": 0}

    def lossy(self, keys, assignment):
        # From the waves on (set-up's seating is the first chunk), the timed
        # path seats half of a batch where its first row went.
        seen["chunks"] += 1
        if seen["chunks"] > 1:
            assignment = assignment.copy()
            assignment[len(keys) // 2:] = assignment[0]
        return real(self, keys, assignment)

    monkeypatch.setattr(JaxObjectPlacement, "_apply_chunk", lossy)
    assert runner.main(args) == 0
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert out[-1]["correct"] is False
    assert any(x.get("check") == "waves.full_member_deviation_seats" and not x["ok"] for x in out)
