"""The control of `correct`: the solves' float arithmetic in the nearest
precision below the one the program computes in (float32 -> bfloat16).

The program has no such switch and gets none. ``install`` wraps, from here,
the jitted solves a run reaches, so that their floating inputs and outputs
pass through bfloat16, and traces ``greedy_balanced_assign`` again with its
cumulative sums carried in bfloat16 (its inputs alone do not show the
precision: seats that are whole multiples of 8 are exact in bfloat16). A
run made with it has to come out ``correct: false``; the benchmark's own
runs never call it.
"""

import functools


def _waterfill_in(dtype, assignment):
    """The program's own ``greedy_balanced_assign``, traced again with every
    cumulative sum of it carried in ``dtype``: its body, as it stands in the
    program, over a ``jnp`` whose ``cumsum`` is lowered."""
    import types

    import jax

    real = assignment.jnp

    class Lowered:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def cumsum(x, *args, **kw):
            return real.cumsum(x.astype(dtype), *args, **kw).astype(x.dtype)

    body = assignment.greedy_balanced_assign.__wrapped__
    return jax.jit(types.FunctionType(
        body.__code__, {**body.__globals__, "jnp": Lowered()}, body.__name__,
        body.__defaults__, body.__closure__,
    ))


def _through(dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np

    def cast(x):
        if isinstance(x, (jax.Array, np.ndarray)) and jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.asarray(x).astype(dtype).astype(x.dtype)
        return x

    def wrap(fn):
        @functools.wraps(fn)
        def lowered(*args, **kw):
            out = fn(*jax.tree.map(cast, args), **jax.tree.map(cast, kw))
            return jax.tree.map(cast, out)

        return lowered

    return wrap


def install(precision: str) -> list[str]:
    """Wrap the solves' entry points; returns the names wrapped."""
    from importlib import import_module

    import jax.numpy as jnp

    import rio_tpu.ops as ops
    from rio_tpu.object_placement import jax_placement as jp

    # By module path: the package re-exports functions under the modules' names.
    assignment, scaling, sinkhorn, structured = (
        import_module(f"rio_tpu.ops.{m}")
        for m in ("assignment", "scaling", "sinkhorn", "structured")
    )

    wrap = _through({"bfloat16": jnp.bfloat16}[precision])
    targets = [
        (sinkhorn, "sinkhorn"),
        (sinkhorn, "exact_quota_repair"),
        (sinkhorn, "plan_rounded_assign"),
        (scaling, "scaling_sinkhorn"),
        (structured, "class_quotas"),
        (structured, "expand_class_quotas"),
    ]
    done = []
    for mod, name in [(assignment, "greedy_balanced_assign"), *targets]:
        orig = getattr(mod, name)
        if name == "greedy_balanced_assign":
            orig_kernel = _waterfill_in({"bfloat16": jnp.bfloat16}[precision], mod)
            lowered = wrap(orig_kernel)
        else:
            lowered = wrap(orig)
        # Every module that imported the name at its top holds the original.
        for holder in (mod, ops, jp):
            if getattr(holder, name, None) is orig:
                setattr(holder, name, lowered)
        done.append(f"{mod.__name__}.{name}")
    return done
