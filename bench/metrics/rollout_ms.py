"""rollout_ms (ms/step): device time of the ``sample`` program -- the
rollout, ``core.rollout``'s scan of velocity forwards and SDE or ODE
steps -- per step of the traced window, found by its XLA module name."""
from bench import trace

MODULE = r"jit__sample"


def read(ctx):
    if not ctx.trace.devices or not ctx.steps:
        return None
    ns = trace.module_ns(ctx.trace, MODULE, ctx.lo, ctx.hi)
    return ns / 1e6 / ctx.steps if ns > 0 else None
