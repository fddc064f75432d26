"""update_ms (ms/step): device time of the ``update`` program -- the
``core.trainers`` loss, its gradient and the ``optim`` AdamW step -- per
step of the traced window, found by its XLA module name."""
from bench import trace

MODULE = r"jit__update"


def read(ctx):
    if not ctx.trace.devices or not ctx.steps:
        return None
    ns = trace.module_ns(ctx.trace, MODULE, ctx.lo, ctx.hi)
    return ns / 1e6 / ctx.steps if ns > 0 else None
