"""Fused GRPO policy-gradient loss — Pallas kernel (TPU target).

Fuses ratio computation, (optional GRPO-Guard RatioNorm), PPO clipping and
the advantage product into one pass over the (T·B,) per-transition arrays.
Block = 1024 rows (padded); a second tiny pass is unnecessary because the
Guard mean is supplied by the caller (it is a batch statistic computed once
per timestep, stop-gradient — see trainers/grpo_guard.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
BLOCK = 1024


def _grpo_kernel(lpn_ref, lpo_ref, adv_ref, mean_ref, loss_ref, frac_ref, *,
                 clip: float, guard: bool):
    lpn = lpn_ref[...].astype(F32)
    lpo = lpo_ref[...].astype(F32)
    adv = adv_ref[...].astype(F32)
    ratio = jnp.exp(jnp.clip(lpn - lpo, -20.0, 20.0))
    if guard:
        ratio = ratio / jnp.maximum(mean_ref[0], 1e-6)
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * adv
    loss_ref[...] = -jnp.minimum(unclipped, clipped)
    frac_ref[...] = (jnp.abs(ratio - 1.0) > clip).astype(F32)


@functools.partial(jax.jit, static_argnames=("clip", "guard", "interpret"))
def grpo_loss(logp_new: jax.Array, logp_old: jax.Array, adv: jax.Array,
              ratio_mean: jax.Array | None = None, *, clip: float = 0.2,
              guard: bool = False, interpret: bool = False):
    """All inputs (B,). Returns (per-sample loss (B,), clip-fraction (B,))."""
    B = logp_new.shape[0]
    blk = min(BLOCK, B)
    pad = (-B) % blk
    def p(a):
        return jnp.pad(a.astype(F32), (0, pad))
    mean = (jnp.ones((1,), F32) if ratio_mean is None
            else jnp.broadcast_to(jnp.asarray(ratio_mean, F32), (1,)))
    n = (B + pad) // blk
    kernel = functools.partial(_grpo_kernel, clip=clip, guard=guard)
    loss, frac = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B + pad,), F32),
            jax.ShapeDtypeStruct((B + pad,), F32),
        ],
        interpret=interpret,
        name="grpo_loss",
    )(p(logp_new), p(logp_old), p(adv), mean)
    return loss[:B], frac[:B]


# ---------------------------------------------------------------------------
# Differentiable wrapper: Pallas kernels carry no autodiff rule, but the
# PPO-clip gradient is closed-form:
#   ∂loss/∂logp_new = −A·ρ·𝟙[active]  with 𝟙[active] = 1 when the unclipped
#   branch is the min, else 1 only inside the clip band (where clip(ρ) moves).
# Forward runs the fused kernel; backward is elementwise jnp.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grpo_loss_diff(logp_new, logp_old, adv, clip: float = 0.2,
                   interpret: bool = False):
    loss, _ = grpo_loss(logp_new, logp_old, adv, None, clip=clip,
                        guard=False, interpret=interpret)
    return loss


def _gld_fwd(logp_new, logp_old, adv, clip, interpret):
    loss = grpo_loss_diff(logp_new, logp_old, adv, clip, interpret)
    return loss, (logp_new, logp_old, adv)


def _gld_bwd(clip, interpret, res, g):
    logp_new, logp_old, adv = res
    ratio = jnp.exp(jnp.clip(logp_new - logp_old, -20.0, 20.0))
    a = adv.astype(F32)
    unclipped = ratio * a
    clipped = jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * a
    in_band = (jnp.abs(ratio - 1.0) <= clip)
    active = jnp.where(unclipped <= clipped, True, in_band)
    gf = g.astype(F32)
    d_lpn = -a * ratio * active.astype(F32) * gf
    d_adv = -jnp.where(unclipped <= clipped, ratio,
                       jnp.clip(ratio, 1.0 - clip, 1.0 + clip)) * gf
    return d_lpn, -d_lpn, d_adv


grpo_loss_diff.defvjp(_gld_fwd, _gld_bwd)
