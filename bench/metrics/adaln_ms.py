"""adaln_ms (ms/step): device time of the adaLN-zero modulation of each
velocity block -- the projection of the condition vector, both RMSNorms
with their shift and scale, the gated residuals -- forward and backward,
per step of the traced window: the operations in the ``jax.named_scope``
``dit_adaln``, in the ``sample`` and ``update`` programs
(``program_trace.scope_ns``)."""
from bench import program_trace

SCOPE = "dit_adaln"


def read(ctx):
    return program_trace.scope_ms_per_step(ctx, SCOPE)
