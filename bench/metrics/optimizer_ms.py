"""optimizer_ms (ms/step): device time of the update's gradient clip and
AdamW step per step of the traced window: the operations in the
``jax.named_scope`` ``optim_adamw`` (``program_trace.scope_ns``)."""
from bench import program_trace

SCOPE = "optim_adamw"


def read(ctx):
    return program_trace.scope_ms_per_step(ctx, SCOPE)
