"""What a wave waits for a busy loop: from the solve thread's last instant to
the coroutine running again (``place.resume``), plus its waits for the
directory's lock (``place.lock_wait``)."""

from benchmark.harness import plugin


def read(run):
    return plugin(run.bench, "layers", "_stages").per_wave_ms(run, ("place.resume", "place.lock_wait"))
