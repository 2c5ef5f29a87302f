"""Seconds from the process's start to the window's: interpreter and jax
start, the cluster, seating, the first full solve, every warm-up and, in a
checkout's first run, compilation."""


def read(run):
    return run.setup_s
