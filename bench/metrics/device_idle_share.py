"""device_idle_share (%): the share of the traced window in which no
operation ran on the device, mean over the chips used."""
from bench import trace


def read(ctx):
    if not ctx.trace.devices or ctx.hi <= ctx.lo:
        return None
    return 100.0 * (1.0 - trace.busy_ns(ctx.trace, ctx.lo, ctx.hi)
                    / (ctx.hi - ctx.lo))
