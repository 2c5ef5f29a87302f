"""Process-level jax settings: the CPU pin for tests, the compile cache.

Two helpers, both idempotent:

* :func:`force_cpu` — pin this process to the CPU backend with N virtual
  devices (the test mesh; ``conftest.py`` and the multichip dry run).
* :func:`compile_cache_dir` — make sure accelerator compiles land in a
  persistent cache whose location can be chosen from outside.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

# <checkout>/.jax_cache: a fixed path (never a tempdir, pid or timestamp),
# so a second process of the same checkout finds what the first compiled.
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_cpu(n_devices: int | None = None) -> None:
    """Pin this process to the CPU backend, for tests.

    ``n_devices`` additionally forces that many virtual CPU devices (the
    multichip-dryrun / sharded-test mesh), raising an existing
    ``xla_force_host_platform_device_count`` flag when it is lower. Must
    run before the first backend use; the env alone is ignored once jax
    is imported, so it is mirrored into live jax config.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            flags = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
        elif int(m.group(1)) < n_devices:
            flags = flags.replace(
                m.group(0), f"--xla_force_host_platform_device_count={n_devices}"
            )
        os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")


def compile_cache_dir() -> str | None:
    """Place jax's persistent compilation cache; returns the directory in use.

    Called wherever a process first builds a device solver
    (``JaxObjectPlacement``, ``chip_smoke.py``, ``bench.py``'s tier entry,
    ``tools/compile_probe.py``). Cold accelerator compiles of the
    directory-sized pipelines take from tens of seconds to minutes, and a
    fresh process otherwise repeats every one of them.

    * ``JAX_COMPILATION_CACHE_DIR`` set: nothing to do — jax reads the
      variable itself, and no code names another directory.
    * unset, accelerator backend: ``<checkout>/.jax_cache``.
    * unset, CPU backend: no cache (returns ``None``). Host compiles are
      seconds, XLA:CPU executables are tied to the compiling host's CPU
      features, and the test suite must not grow the checkout.

    Touches the backend (``jax.default_backend()``), so call it where the
    first solve would initialize it anyway, not at import.
    """
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.default_backend() == "cpu":
        return None
    path = str(_DEFAULT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
