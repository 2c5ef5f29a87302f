"""What of a wave no stage names: the harness's ``bench.wave`` span minus the
union of every leaf stage and full collection inside it, mean per wave."""

from benchmark.harness import plugin


def read(run):
    st = plugin(run.bench, "layers", "_stages")
    return st.unattributed_ms(st.wave_spans(run), st.records(run))
