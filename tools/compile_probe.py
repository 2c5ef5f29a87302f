"""AOT compile-time ladder for the placement pipelines.

The r5 finding (not re-measured beyond what CLAUDE.md records): the TPU
backend's XLA compile time is superlinear in the flat object-row count
while CPU XLA stays flat. This probe times `jit(...).lower().compile()` —
no execution — across a size ladder for each pipeline, printing one JSON
line per (pipeline, size). The thresholds it informs are
`_HIER_CHUNK_ROWS` and `_FLAT_REBALANCE_MAX_ROWS` in
`rio_tpu/object_placement/jax_placement.py`.

    env PYTHONPATH=. JAX_PLATFORMS=cpu python tools/compile_probe.py      # CPU control
    python tools/compile_probe.py --sizes 524288,1048576                  # on the chip

It places the persistent compile cache like every entry point
(`rio_tpu.utils.jaxenv.compile_cache_dir`) and prints how many entries
the cache held at start: a compile time is a cold one only where that
says 0 or each line's `cache_hit` is false. Bound a run from outside
(`timeout`, the chip tool's own limit).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CACHE_HITS = [0]


def _timed_compile(pipeline: str, n: int, lower) -> dict:
    hits = _CACHE_HITS[0]
    t0 = time.perf_counter()
    lowered = lower()
    t1 = time.perf_counter()
    lowered.compile()
    t2 = time.perf_counter()
    return {"pipeline": pipeline, "n": n, "lower_s": round(t1 - t0, 1),
            "compile_s": round(t2 - t1, 1), "cache_hit": _CACHE_HITS[0] > hits}


def _hier_shapes(n: int):
    import jax
    import jax.numpy as jnp

    d, m = 16, 1024
    return [jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((d, m), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.float32),
            jax.ShapeDtypeStruct((m,), jnp.float32)]


def probe_hier(n: int) -> dict:
    from rio_tpu.parallel.hierarchical import hierarchical_assign

    return _timed_compile(
        "hier_flat", n,
        lambda: hierarchical_assign.lower(*_hier_shapes(n), n_groups=32),
    )


def probe_hier_chunked(n: int, chunk: int = 524_288) -> dict:
    from rio_tpu.parallel.hierarchical import chunked_hierarchical_assign

    if n % chunk or n == chunk:
        return {"pipeline": "hier_chunked", "n": n, "skipped": "not a chunk multiple"}
    return _timed_compile(
        "hier_chunked", n,
        lambda: chunked_hierarchical_assign.lower(
            *_hier_shapes(n), n_groups=32, n_chunks=n // chunk
        ),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="262144,524288,1048576,2097152")
    ap.add_argument("--pipelines", default="hier_flat,hier_chunked")
    args = ap.parse_args()
    import jax

    from rio_tpu.utils.jaxenv import compile_cache_dir

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _CACHE_HITS[0] += 1

    jax.monitoring.register_event_listener(on_event)
    cache = compile_cache_dir()
    entries = len(os.listdir(cache)) if cache and os.path.isdir(cache) else 0
    print(json.dumps({"backend": jax.default_backend(),
                      "device": str(jax.devices()[0]),
                      "device_kind": jax.devices()[0].device_kind,
                      "compile_cache_dir": cache,
                      "compile_cache_entries_at_start": entries}), flush=True)
    for n in (int(x) for x in args.sizes.split(",")):
        for p in args.pipelines.split(","):
            fn = {"hier_flat": probe_hier, "hier_chunked": probe_hier_chunked}[p]
            print(json.dumps(fn(n)), flush=True)


if __name__ == "__main__":
    main()
