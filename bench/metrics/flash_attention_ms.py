"""flash_attention_ms (ms/step): device time of the flash attention kernel
-- the scores, softmax and PV of each velocity block's bidirectional
attention, ``pallas_call(name="flash_attention")`` -- in the ``sample``
and ``update`` programs, per step of the traced window: the operations
whose instruction is named ``flash_attention(\\.\\d+)?``
(``trace.op_events``), mean over devices.  None where no such operation
ran, as in a program that keeps the attention in XLA."""
from bench import trace

KERNEL = r"flash_attention(\.\d+)?$"


def read(ctx):
    if not ctx.trace.devices or not ctx.steps:
        return None
    events = trace.op_events(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    if not events:
        return None
    ns = sum(e.dur for e in events) / len(ctx.trace.devices)
    return ns / 1e6 / ctx.steps
