"""Device busy time inside a wave's ``assign_batch`` span, from the trace:
the mean over the window's waves, in milliseconds."""


def read(run):
    if run.trace is None:
        return None
    spans = [s for s in run.trace["harness_spans"] if s[0] == "bench.wave.assign_batch"]
    if not spans:
        return None
    busy = 0.0
    for _, a, b in spans:
        busy += sum(max(0.0, min(b, e) - max(a, s)) for s, e, _ in run.trace["device_intervals"])
    return busy / len(spans) / 1e6
