"""mlp_ms (ms/step): device time of the gated MLP of each velocity block,
forward and backward, per step of the traced window: the operations in the
``jax.named_scope`` ``dit_mlp``, in the ``sample`` and ``update`` programs
(``program_trace.scope_ns``)."""
from bench import program_trace

SCOPE = "dit_mlp"


def read(ctx):
    return program_trace.scope_ms_per_step(ctx, SCOPE)
