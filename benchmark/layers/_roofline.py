"""A kernel's share of its roofline: the least time the chip could take for
the bytes and operations the call needs (``costs/<kernel>.py``) over the
device time of one call (``peaks.json``, by ``device_kind``)."""

from benchmark.harness import HERE, load_json, plugin


def share(run, kernel: str, **shape):
    if run.trace is None:
        return None
    prog = run.trace["programs"].get(kernel)
    if not prog or not prog["calls"]:
        return None
    peaks = load_json(HERE / "peaks.json")["devices"]
    kind = run.trace["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    need = plugin(run.bench, "costs", kernel).cost(**shape)
    compute_s = need["flops"] / peaks[kind]["flops_per_s"]
    memory_s = need["bytes"] / peaks[kind]["bytes_per_s"]
    run.log.setdefault("roofline_bound", {})[kernel] = (
        "memory" if memory_s >= compute_s else "compute"
    )
    return 100.0 * max(compute_s, memory_s) / (prog["seconds"] / prog["calls"])


def po2(n: int, minimum: int) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b
