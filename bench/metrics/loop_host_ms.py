"""loop_host_ms (ms/step): the host's own work in the train loop per
step of the traced window -- the time in the program's ``repro.step``
spans less the time in their ``repro.loop.fetch`` spans, the blocking wait
for the step's metrics (``program_trace.host_ns``)."""
from bench import program_trace


def read(ctx):
    if not ctx.trace.devices or not ctx.steps:
        return None
    tr = program_trace.trace_of(ctx)
    if tr is None:
        return None
    ns = program_trace.host_ns(tr, ctx.lo, ctx.hi)
    return ns / 1e6 / ctx.steps if ns > 0 else None
