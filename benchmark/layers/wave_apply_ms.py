"""Host time of a wave's ``_apply_chunk`` (``place.apply``): the per-key
directory writes, on the event loop."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").per_wave_ms(run, ("place.apply",))
