"""AdamW over pytrees (no optax on the image — built from scratch).

Memory policy: moments in f32 regardless of param dtype (bf16 params,
f32 state — the standard mixed-precision training layout); the update is
computed in f32 and cast back to the param dtype.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.config import OptimConfig

F32 = jnp.float32


class AdamWState(NamedTuple):
    step: jax.Array     # () int32
    mu: dict            # first moments (f32, same tree as params)
    nu: dict            # second moments (f32)


def adamw_init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, F32)
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      mu=jax.tree.map(zeros, params),
                      nu=jax.tree.map(zeros, params))


def adamw_update(params, grads, state: AdamWState, cfg: OptimConfig,
                 lr: jax.Array) -> Tuple[dict, AdamWState]:
    with jax.named_scope("optim_adamw"):
        b1, b2 = cfg.betas
        step = state.step + 1
        c1 = 1.0 - b1 ** step.astype(F32)
        c2 = 1.0 - b2 ** step.astype(F32)

        def upd(p, g, m, v):
            gf = g.astype(F32)
            m2 = b1 * m + (1 - b1) * gf
            v2 = b2 * v + (1 - b2) * gf * gf
            mhat = m2 / c1
            vhat = v2 / c2
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if cfg.weight_decay:
                delta = delta + cfg.weight_decay * p.astype(F32)
            return (p.astype(F32) - lr * delta).astype(p.dtype), m2, v2

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        out = [upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return new_p, AdamWState(step=step, mu=new_m, nu=new_v)
