"""Run one cell of the benchmark once: load, warm, measure, check, print.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<mix>.json``).
Everything that belongs to one configuration, one mix, one kind of traffic,
one audit or one metric is a file of its own, found by name; adding a cell
adds files and entries and edits nothing here.

Needs a TPU: without one it exits 2 and prints nothing on standard output.
``--rehearse-on-cpu`` (never the default) runs the same control flow on the
CPU backend at the sizes the configuration's ``rehearsal`` block gives and
stamps every line it prints ``"rehearsal": true``; a rehearsal's numbers
are not measurements. ``--control bfloat16`` (never the default) passes the
solves' float arithmetic through bfloat16: the run has to come out
``correct: false``.

Standard output is JSON lines; the last is the result.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402 - stamps the process's start first

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import shutil  # noqa: E402

import numpy as np  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--control", choices=("bfloat16",), default=None)
    ap.add_argument("--rate-per-s", type=float, default=None,
                    help="override the open-loop rate (the rate sweep only)")
    return ap.parse_args(argv)


def load_cell(args):
    bench = harness.load_json(harness.REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    cell = cells.get(args.workload)
    if cell is None:
        # A mix that no cell uses yet runs under its configuration's name too
        # (the rate sweep's traffic is such a one): <config>.<mix>.
        name = next((c for c in configs if args.workload.startswith(c + ".")), None)
        if name is None:
            raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
        cell = {"name": args.workload, "config": name,
                "traffic": args.workload[len(name) + 1:], "chips": 1}
    config = harness.load_json(harness.REPO / configs[cell["config"]]["file"])
    mix = harness.load_json(harness.find_file(bench, "traffic", cell["traffic"], (".json",)))
    if args.rehearse_on_cpu:
        config = {**config, **config.get("rehearsal", {})}
        mix = {**mix, "generators": [
            {**g, **g.get("rehearsal", {})} for g in mix["generators"]
        ]}
    if args.rate_per_s is not None:
        mix = {**mix, "generators": [
            {**g, "rate_per_s": args.rate_per_s} if g["kind"] == "open_loop" else g
            for g in mix["generators"]
        ]}
    return bench, cell, config, mix


async def run_audits(run, phase: str, names: list) -> None:
    for name in names:
        with run.span(f"bench.audit.{name}"):
            await harness.plugin(run.bench, "audits", name).audit(run, phase)


async def run_cell(run) -> None:
    import jax

    args, cfg, mix = run.args, run.config, run.mix
    run.app = harness.plugin(run.bench, "apps", cfg["app"])
    gens = [(g, harness.plugin(run.bench, "traffic", g["kind"])) for g in mix["generators"]]
    run.cluster = await harness.build_cluster(run)
    trace_dir = None
    try:
        harness.note("cluster up; seating")
        await harness.seat_all(run)
        run.log["seats0"] = await run.cluster.seats()
        await run_audits(run, "after_setup", cfg["audits"]["after_setup"])
        harness.note("seated and audited; warming")
        for g, mod in gens:
            with run.span(f"bench.warm.{g['name']}"):
                await mod.warm(run, g)
        # -- the window ------------------------------------------------------
        if args.trace:
            trace_dir = str(harness.REPO / "chiprun_out" / "trace" / f"{args.workload}.{args.seed}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # The window starts from a collected heap: what set-up left behind
        # is not the window's garbage.
        gc.collect()
        for k in harness.GC_PAUSES:
            harness.GC_PAUSES[k] = [0, 0, 0] if k == "count" else [0.0, 0.0, 0.0]
        run.log["red0"] = run.cluster.red_rows()
        run.log["daemons0"] = run.cluster.daemon_totals()
        compiles0 = harness.COMPILES["backend_compiles"]
        run.setup_s = time.perf_counter() - harness.T0
        marks = {}
        with jax.profiler.TraceAnnotation("bench.window.start"):
            marks["bench.window.start"] = time.perf_counter_ns()
        t_start = time.perf_counter()
        t_end = t_start + args.seconds
        run.window = (t_start, t_end)
        harness.note(f"window open for {args.seconds} s (set-up {run.setup_s:.1f} s)")
        await asyncio.gather(*(mod.drive(run, g, t_start, t_end) for g, mod in gens))
        with jax.profiler.TraceAnnotation("bench.window.end"):
            marks["bench.window.end"] = time.perf_counter_ns()
        run.log["compiles_in_window"] = harness.COMPILES["backend_compiles"] - compiles0
        run.log["gc_in_window"] = harness.gc_snapshot()
        run.log["red1"] = run.cluster.red_rows()
        run.log["daemons1"] = run.cluster.daemon_totals()
        if args.trace:
            jax.profiler.stop_trace()
            from benchmark import trace_reduce

            run.trace = trace_reduce.reduce(
                trace_reduce.find_xplane(trace_dir), run.spans, marks
            )
            run.trace["device_kind"] = jax.devices()[0].device_kind
            shutil.rmtree(trace_dir, ignore_errors=True)
        harness.note("window closed; waiting for quiescence")
        # -- the verdict, at quiescence ---------------------------------------
        if await harness.quiesce(run):
            await run_audits(run, "after_window", mix["audits_after_window"])
        run.check("compiles_in_window", run.log["compiles_in_window"], 0)
    finally:
        await run.cluster.close()
        if run.cluster.state_path:
            shutil.rmtree(os.path.dirname(run.cluster.state_path), ignore_errors=True)


def result_line(run) -> dict:
    bench, cell, args = run.bench, run.cell, run.args
    metrics = {}
    section = "per_layer" if args.trace else "end_to_end"
    sub = "layers" if args.trace else "end_to_end"
    for m in bench[section]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = harness.plugin(bench, sub, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = failed = 0
    for g in run.log.values():
        if isinstance(g, dict) and "ok" in g:
            attempted += int(g["ok"].shape[0])
            failed += int((~g["ok"]).sum())
    device = harness.device_record()
    out = {
        "correct": bool(run.checks) and all(c["ok"] for c in run.checks) and not run.failures,
        "attempted": attempted, "failed": failed, "metrics": metrics, "device": device,
    }
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {
            "device_ops": run.trace["device_ops"], "idle_gaps": run.trace["idle_gaps"],
        }
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.seconds <= 0:
        raise SystemExit("--seconds must be positive")
    stamp = {}
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        stamp = {"rehearsal": True, "platform": "cpu"}
    bench, cell, config, mix = load_cell(args)
    import jax

    backend = jax.default_backend()
    if not args.rehearse_on_cpu and (backend != "tpu" or len(jax.devices()) < cell["chips"]):
        print(
            f"benchmark: backend {backend!r} with {len(jax.devices())} device(s); the cell "
            f"needs a TPU with {cell['chips']} chip(s). Nothing measured "
            "(--rehearse-on-cpu rehearses the control flow at a tiny size).",
            file=sys.stderr,
        )
        return 2
    if stamp:
        plain = harness.emit
        harness.emit = lambda rec: plain({**rec, **stamp})
    from rio_tpu.utils.jaxenv import compile_cache_dir

    harness.watch_compiles()
    harness.watch_gc()
    cache_dir = compile_cache_dir()
    if args.control:
        from benchmark import lowprec

        lowprec.install(args.control)
    run = harness.Run(args=args, bench=bench, cell=cell, config=config, mix=mix,
                      rehearsal=args.rehearse_on_cpu)
    devices = jax.devices()
    harness.emit({
        "env": {
            "platform": devices[0].platform, "device_kind": devices[0].device_kind,
            "device_count": len(devices), "host_cpu_count": os.cpu_count(),
            "jax": jax.__version__, "compile_cache_dir": cache_dir,
        },
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "control": args.control,
    })
    asyncio.run(run_cell(run))
    line = result_line(run)
    timed = [g for g in run.log.values() if isinstance(g, dict) and "due" in g]
    lat = np.concatenate([(g["done"] - g["due"]) * 1e3 for g in timed]) if timed else np.zeros(0)
    half = [
        ((g["done"] - g["due"]) * 1e3)[g["due"] > (run.window[0] + run.window[1]) / 2]
        for g in timed
    ]
    half = np.sort(np.concatenate(half)) if half else np.zeros(1)
    harness.emit({
        "summary": {
            "setup_s": run.setup_s, "window_s": run.window[1] - run.window[0],
            "request_samples": int(lat.shape[0]),
            "request_p50_ms": float(np.sort(lat)[lat.shape[0] // 2]) if lat.shape[0] else None,
            # A median that climbs with the window is a backlog that grows.
            "request_p50_ms_second_half": float(half[half.shape[0] // 2]),
            "compiles": dict(harness.COMPILES),
            "compiles_in_window": run.log.get("compiles_in_window"),
            "gc_in_window": run.log.get("gc_in_window"),
            "first_solve": run.log.get("first_solve"), "seat_s": run.log.get("seat_s"),
            "quiesce_s": run.log.get("quiesce_s"),
            "roofline_bound": run.log.get("roofline_bound"),
            "daemon_rebalances_in_window": {
                k: run.log["daemons1"].get(k, 0) - run.log["daemons0"].get(k, 0)
                for k in ("rebalances", "rebalances_discarded", "liveness_changes")
            } if "daemons1" in run.log else None,
            "failures": run.failures,
        }
    })
    if args.control:
        line["control"] = args.control
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
