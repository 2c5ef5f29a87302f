"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, device time per program, the device operations that took most time,
and the longest idle gaps named by what the host was doing.

Read with nothing but jax (``jax.profiler.ProfileData``). What a v5e trace
holds (looked at by hand, PR 23): a plane ``/device:TPU:<n>`` per chip whose
line ``XLA Modules`` has one event per execution of a jitted program, named
``jit_<function>(<fingerprint>)``, and whose line ``XLA Ops`` has one event
per HLO operation; a plane ``/host:CPU`` whose lines are host threads, with
``jax.profiler.TraceAnnotation`` names on the thread that made them. All
starts and durations are nanoseconds on one clock. The CPU backend has no
device plane: its executions sit on host lines named
``tf_XLAPjRtCpuClient/...``, which a rehearsal reads as its device.
"""

import glob
import os
import re

WINDOW_START = "bench.window.start"
WINDOW_END = "bench.window.end"
HOST_SPAN_PREFIXES = ("bench.", "rio_tpu.")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    """``jit__class_refresh_device(123)`` -> ``_class_refresh_device``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce(path: str, harness_spans=(), marks=None) -> dict:
    """Reduce one trace.

    ``harness_spans`` are ``(name, t0_ns, t1_ns)`` on the caller's own clock
    and ``marks`` maps :data:`WINDOW_START` / :data:`WINDOW_END` to the
    caller's clock at the instants it made those annotations: they give the
    offset between the two clocks and the window. Without marks the window
    is the whole trace and the caller's spans are left out.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_lines: dict = {}  # device -> {"modules": [...], "ops": [...]}
    host_spans: list = []
    cpu_exec: list = []
    found: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = device_lines.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] += [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                is_exec = line.name.startswith("tf_XLAPjRtCpuClient")
                for e in line.events:
                    if e.name in (WINDOW_START, WINDOW_END):
                        found.setdefault(e.name, e.start_ns)
                    elif e.name.startswith(HOST_SPAN_PREFIXES):
                        host_spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                    elif is_exec and e.duration_ns > 0 and not e.name.startswith(("end:", "Threadpool")):
                        cpu_exec.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if not device_lines and cpu_exec:
        device_lines["/host:CPU (rehearsal)"] = {"modules": cpu_exec, "ops": cpu_exec}
    if not device_lines:
        # Nothing ran on a device (a CPU rehearsal whose window solved on
        # the host): the window is still the marks'.
        device_lines["(none)"] = {"modules": [], "ops": []}

    everything = [t for d in device_lines.values() for iv in d["modules"] for t in iv[:2]]
    lo, hi = (min(everything), max(everything)) if everything else (0, 0)
    offset = None
    if marks and WINDOW_START in found and WINDOW_END in found:
        lo, hi = found[WINDOW_START], found[WINDOW_END]
        offset = (
            (lo - marks[WINDOW_START]) + (hi - marks[WINDOW_END])
        ) / 2.0
        host_spans += [(n, a + offset, b + offset) for n, a, b in harness_spans]
    window_ns = hi - lo

    busy_ns = 0.0
    programs: dict = {}
    ops: dict = {}
    intervals: list = []
    for dev in device_lines.values():
        clipped = []
        for s, e, name in dev["modules"]:
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            clipped.append(c)
            intervals.append((c[0], c[1], program_name(name)))
            p = programs.setdefault(program_name(name), {"seconds": 0.0, "calls": 0})
            p["seconds"] += (c[1] - c[0]) / 1e9
            p["calls"] += 1
        busy_ns += sum(e - s for s, e in _union(clipped))
        for s, e, name in dev["ops"]:
            c = _clip(s, e, lo, hi)
            if c is not None:
                key = name.split(" = ")[0].lstrip("%")
                ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) / 1e9
    n_dev = len(device_lines)

    # Idle gaps of the first device, each named by the shortest host span
    # that covers its middle (the window itself where none does).
    first = sorted(device_lines)[0]
    merged = _union(
        [c for s, e, _ in device_lines[first]["modules"] if (c := _clip(s, e, lo, hi))]
    )
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    named: dict = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:64]:
        mid = (s + e) / 2
        cover = [(b - a, n) for n, a, b in host_spans if a <= mid <= b]
        name = min(cover)[1] if cover else "bench.window"
        named[name] = named.get(name, 0.0) + (e - s) / 1e9

    def top(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "devices": n_dev,
        "programs": programs,
        "device_ops": top(ops),
        "idle_gaps": top(named),
        "device_intervals": intervals,
        "harness_spans": [(n, a + offset, b + offset) for n, a, b in harness_spans] if offset is not None else [],
        "clock_offset_ns": offset,
    }
