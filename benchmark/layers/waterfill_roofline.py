"""``greedy_balanced_assign`` at the wave's bucket against its roofline.
Memory-bound by the count in costs/."""

from benchmark.harness import plugin


def read(run):
    r = plugin(run.bench, "layers", "_roofline")
    sizes = [
        g["wave_size"] for g in run.mix["generators"]
        if g["kind"] == "waves"
    ]
    if not sizes:
        return None
    return r.share(
        run, "greedy_balanced_assign",
        rows=r.po2(sizes[0], 256), cols=r.po2(run.config["nodes"], 64),
    )
