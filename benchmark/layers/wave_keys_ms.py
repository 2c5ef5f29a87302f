"""Host time a wave spends making key strings (``place.keys``) and on the
membership passes over them (``place.filter``), per wave, from the program's
stage log."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").per_wave_ms(run, ("place.keys", "place.filter"))
