"""Median time of one ``save_state`` from the servers' merged RED histograms
(the program records its saves under ``("rio.State", "save")``), over the
window, interpolated as ``_red.py`` interpolates a handler's median."""

from types import SimpleNamespace

from benchmark.harness import plugin

KEY = ("rio.State", "save")


def read(run):
    as_handler = SimpleNamespace(app=SimpleNamespace(HANDLER=KEY), log=run.log)
    return plugin(run.bench, "layers", "_red").handler_p50_ms(as_handler)
