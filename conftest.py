"""Repo-level pytest config: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharded-solver tests run on
``xla_force_host_platform_device_count=8`` CPU devices instead (the same
mechanism the driver's ``dryrun_multichip`` uses). Must run before the first
``import jax`` anywhere in the test session.
"""

import asyncio
import inspect
import sys

sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))

# Hard override: whatever platform the environment names, tests always run
# on the virtual 8-device CPU mesh (the suite must pass on a machine that
# holds a chip without taking it).
from rio_tpu.utils.jaxenv import force_cpu  # noqa: E402

force_cpu(n_devices=8)


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run async test on a fresh event loop")
    config.addinivalue_line(
        "markers",
        "slow: long-running test (1M-actor stress, soak, multihost); "
        "tier-1 verify runs -m 'not slow'",
    )


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on a fresh event loop (no pytest-asyncio dep)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
            if name in pyfuncitem.funcargs
        }
        asyncio.run(fn(**kwargs))
        return True
    return None
