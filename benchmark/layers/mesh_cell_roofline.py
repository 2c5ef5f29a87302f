"""The mesh x chunk cell program (``mesh_cell_solve``: one two-level solve of
a cell's rows on one device) against its roofline."""

from benchmark.harness import plugin


def read(run):
    mesh = plugin(run.bench, "layers", "_mesh")
    done = mesh.replans(run)
    if not done:
        return None
    solver = run.config["solver"]
    cells = max(1, done[-1]["devices"]) * max(1, done[-1]["chunks"])
    return plugin(run.bench, "layers", "_roofline").share(
        run, mesh.PROGRAM, rows=len(run.cluster.names) // cells, feat=solver["features"],
        nodes=run.config["nodes"], group_size=solver["group_size"], iters=solver["iters"],
    )
