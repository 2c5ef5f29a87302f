"""Median of what a mesh solve holds the servers' loop for:
``solve.snapshot`` (four million keys and seats under the directory's lock)
plus ``solve.apply``."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_mesh").mesh_calls_ms(
        run, ("solve.snapshot", "solve.apply")
    )
