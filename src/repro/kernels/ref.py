"""Pure-jnp oracles for every Pallas kernel (the correctness contract the
shape/dtype sweep tests assert against)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30
LOG2PI = math.log(2.0 * math.pi)     # host constant, see core/schedulers.py


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0
                        ) -> jax.Array:
    """q: (B,Sq,H,D); k/v: (B,Sk,K,D/Dv) -> (B,Sq,H,Dv)."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, D).astype(F32)
    s = jnp.einsum("bskgd,btkd->bskgt", qg, k.astype(F32)) * (D ** -0.5)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= kp <= qp
    if window > 0:
        ok &= kp > qp - window
    s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bskgt,btkd->bskgd", p, v.astype(F32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def ssd_scan_ref(x, dt, a, bm, cm, *, init_state=None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Sequential (non-chunked) SSD recurrence — the ground truth.

    x: (B,L,H,P); dt: (B,L,H); a: (H,); bm/cm: (B,L,N).
    Returns (y (B,L,H,P), final_state (B,H,P,N))."""
    B, L, H, P = x.shape
    N = bm.shape[-1]
    h0 = (jnp.zeros((B, H, P, N), F32) if init_state is None
          else init_state.astype(F32))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp           # (B,H,P),(B,H),(B,N),(B,N)
        dA = jnp.exp(dt_t.astype(F32) * a.astype(F32))          # (B,H)
        h = h * dA[..., None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dt_t.astype(F32), b_t.astype(F32),
            x_t.astype(F32))
        y = jnp.einsum("bhpn,bn->bhp", h, c_t.astype(F32))
        return h, y

    hT, ys = jax.lax.scan(step, h0,
                          (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                           bm.swapaxes(0, 1), cm.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(x.dtype), hT


def sde_step_ref(v, x, t, t_next, eps, *, eta: float
                 ) -> Tuple[jax.Array, jax.Array]:
    """Flow-SDE Euler–Maruyama step + Gaussian log-prob (paper Eq. 1).

    v, x, eps: (B, ...); t, t_next: scalars.  Returns (x_next, logp (B,))."""
    xf, vf = x.astype(F32), v.astype(F32)
    # σ argument clamped (FlowSDEScheduler.t_sigma_max); drift uses raw t
    tc = jnp.clip(t, 1e-4, 0.96)
    sigma = eta * jnp.sqrt(tc / (1.0 - tc))
    delta = t - t_next
    drift = vf + (sigma ** 2 / (2.0 * t)) * (xf + (1.0 - t) * vf)
    mean = xf - drift * delta
    std = sigma * jnp.sqrt(delta)
    x_next = mean + std * eps.astype(F32)
    z = (x_next - mean) / std
    logp = (-0.5 * (z * z + LOG2PI) - jnp.log(std))
    return x_next, logp.reshape(x.shape[0], -1).sum(-1)


def grpo_loss_ref(logp_new, logp_old, adv, *, clip: float,
                  guard: bool = False) -> Tuple[jax.Array, jax.Array]:
    """PPO-clip objective per sample (optionally GRPO-Guard RatioNorm).

    logp_new/logp_old/adv: (B,). Returns (per-sample loss, clip fraction)."""
    ratio = jnp.exp(jnp.clip(logp_new - logp_old, -20.0, 20.0))
    if guard:
        ratio = ratio / jnp.maximum(
            jax.lax.stop_gradient(ratio.mean()), 1e-6)
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1.0 - clip, 1.0 + clip) * adv
    loss = -jnp.minimum(unclipped, clipped)
    frac = (jnp.abs(ratio - 1.0) > clip).astype(F32)
    return loss, frac
