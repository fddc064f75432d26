"""step_mfu (%): model FLOPs of the velocity passes each step makes
(``bench.flops.step_flops``, from the parameter shapes; backward counted
as twice the forward, recomputation not counted) times the steps of the
traced window, over the window and the chips' bf16 peak."""
from bench import flops


def read(ctx):
    if not ctx.peaks or not ctx.steps or ctx.hi <= ctx.lo:
        return None
    t, c = ctx.traffic, ctx.config
    passes = {k: t[v] if isinstance(v, str) else v
              for k, v in c["step_passes"].items()}
    per_step = flops.step_flops(
        ctx.shapes, ctx.batch, t["latent_tokens"],
        c["run"]["data"]["encoder"]["cond_len"],
        passes["velocity_forward"], passes["velocity_forward_backward"])
    chips = len(ctx.trace.devices)
    return 100.0 * per_step * ctx.steps / ctx.window_s / (
        chips * ctx.peaks["bf16_flops_per_s"])
