"""How long a wave holds the event loop the servers share: the stages' self
time on the loop thread (waits left out), mean per wave."""

from benchmark.harness import plugin


def read(run):
    st = plugin(run.bench, "layers", "_stages")
    return st.loop_held_ms(st.wave_spans(run), st.records(run))
