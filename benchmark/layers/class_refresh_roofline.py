"""The delta route's warm class refresh (``_class_refresh_device``: the
M x M potentials re-converged after a flip) against its roofline."""

from benchmark.harness import plugin

# JaxObjectPlacement runs max(4, min(8, n_iters)) iterations, n_iters 30 by default.
ITERS = 8


def read(run):
    r = plugin(run.bench, "layers", "_roofline")
    return r.share(run, "_class_refresh_device", m=r.po2(run.config["nodes"], 64), iters=ITERS)
