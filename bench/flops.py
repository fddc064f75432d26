"""Operation counts the per-layer metrics divide by.

``velocity_flops`` counts one forward pass of the DiT velocity field from
the shapes of its parameter tree: every matrix product on the token path
(latent and condition projections in, q/k/v/o, the gated MLP, the latent
projection out), the S x S attention products, and the per-sample timestep
MLP and adaLN modulation.  Elementwise work (norms, softmax, rotary, SiLU)
is not counted.  The token embedding and LM head of the backbone are not
part of the velocity and are not counted.
"""
from __future__ import annotations

from typing import Dict, Tuple

Shapes = Dict[str, Tuple[int, ...]]


def velocity_flops(shapes: Shapes, batch: int, latent_tokens: int,
                   cond_len: int) -> float:
    """Multiply-adds x 2 of one velocity forward over ``batch`` samples."""
    B, Lt, Lc = batch, latent_tokens, cond_len
    S = Lc + Lt
    ld, d = shapes["latent_in"]
    cd = shapes["cond_proj"][0]
    f = 2.0 * B * (Lt * ld * d + Lc * cd * d + 2 * d * d + Lt * d * ld)
    L, _, H, hd = shapes["backbone/blocks/attn/wq"]
    K = shapes["backbone/blocks/attn/wk"][2]
    ff = shapes["backbone/blocks/ffn/w_gate"][2]
    six_d = shapes["backbone/blocks/ada"][2]
    per_layer = (d * six_d                       # adaLN, once per sample
                 + S * d * (2 * H * hd + 2 * K * hd)   # q, o and k, v
                 + 2 * S * S * H * hd            # scores and mixing
                 + 3 * S * d * ff)               # gate, up, down
    return f + 2.0 * B * L * per_layer


def step_flops(shapes: Shapes, batch: int, latent_tokens: int,
               cond_len: int, forward: int, forward_backward: int) -> float:
    """Model FLOPs of one RL step: ``forward`` velocity passes in the
    rollout and ``forward_backward`` in the update, the backward counted as
    twice the forward; recomputation under remat is not counted."""
    one = velocity_flops(shapes, batch, latent_tokens, cond_len)
    return one * (forward + 3 * forward_backward)

