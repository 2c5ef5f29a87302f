"""Wall time of a wave's ``place.solve`` (the worker thread: cost build,
dispatch, device, transfer back, routing). Beside ``place_device_ms.wave``
it prices dispatch and transfer (ROADMAP Queue 1 item 6)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").per_wave_ms(run, ("place.solve",))
