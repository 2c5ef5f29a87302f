"""Device time of the cell program, per device and per re-plan of the
window, in milliseconds (``run.trace["programs"]`` sums over the devices'
planes)."""

from benchmark.harness import plugin


def read(run):
    mesh = plugin(run.bench, "layers", "_mesh")
    if run.trace is None:
        return None
    prog = run.trace["programs"].get(mesh.PROGRAM)
    n = len(mesh.replans(run))
    if not prog or not prog["calls"] or not n:
        return None
    return prog["seconds"] * 1e3 / run.trace["devices"] / n
