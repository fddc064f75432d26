"""rewards_ms (ms/step): device time of the ``rewards`` program -- the
``core.rewards`` towers and the advantages -- per step of the traced
window, found by its XLA module name.  The system jits a
``functools.partial`` there, which XLA names ``jit__unknown``."""
from bench import trace

MODULE = r"jit__(rewards|unknown)"


def read(ctx):
    if not ctx.trace.devices or not ctx.steps:
        return None
    ns = trace.module_ns(ctx.trace, MODULE, ctx.lo, ctx.hi)
    return ns / 1e6 / ctx.steps if ns > 0 else None
