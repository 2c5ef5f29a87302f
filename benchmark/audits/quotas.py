"""After a committed flat solve every node holds its exact
largest-remainder share (rule 2: the capacity vector is recovered from the
loads, never read from the program)."""

from benchmark.reference import quotas


async def audit(run, phase: str) -> None:
    c = run.cluster
    counts, active = run.log[f"counts.{phase}"], run.log[f"active.{phase}"]
    if run.rehearsal:
        # The CPU rehearsal solves in the greedy mode, which promises the
        # ceiling and no floor; the exact audit is the chip's.
        return
    cap = quotas.infer_capacity(counts, active, c.live_idx)
    run.check(f"{phase}.quota_miss_seats", quotas.miss(counts, cap), 0)
