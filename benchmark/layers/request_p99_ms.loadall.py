"""``request_p99_ms`` of the closed-loop cell, as a per-layer metric: the
host's own hiccups reach this tail (111-166 ms over 12 chip runs, one set
spreading by a quarter, PR 23), too wide for a bound the contract allows.
Read on the traced run, so with the profiler's cost in it."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "end_to_end", "request_p99_ms").read(run)
