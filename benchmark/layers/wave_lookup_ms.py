"""Host time a wave spends reading seats back: ``assign_batch``'s final
address list (``place.resolve``) and the harness's ``lookup_batch``
(``place.lookup``)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").per_wave_ms(run, ("place.lookup", "place.resolve"))
