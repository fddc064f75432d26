"""attention_ms (ms/step): device time of the attention of each velocity
block -- QKV and output projections, qk-norm, RoPE, scores and softmax,
forward and backward -- per step of the traced window: the operations in
the ``jax.named_scope`` ``dit_attention``, in the ``sample`` and
``update`` programs (``program_trace.scope_ns``)."""
from bench import program_trace

SCOPE = "dit_attention"


def read(ctx):
    return program_trace.scope_ms_per_step(ctx, SCOPE)
