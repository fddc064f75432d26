"""Fused Flow-SDE sampling step (paper Eq. 1) — Pallas kernel (TPU target).

The RL sampling loop applies this elementwise update T times per trajectory;
it is bandwidth-bound (5 streams: v, x, ε in; x_next, logp out), so fusing
drift + noise injection + Gaussian log-density + the per-sample reduction
into one VMEM pass removes three HBM round-trips vs. the unfused XLA graph.

Layout: grid = one program per batch row.  Each row's flattened latent
(Lt·ld floats) is viewed as ``(rows, 128)`` — zero-padded up to a multiple
of 128 lanes — so every block's last two dimensions span the whole array,
which the TPU's (8, 128) tiling accepts for any row count.  At FLUX's packed
512×512 latent (Lt·ld = 1024·64 = 64 K floats = 256 KB) one block is a
(512, 128) tile set; three inputs and one output, double-buffered, stay
near 2 MB of VMEM.  The per-call scalars (t, Δt, σ√Δt, the drift
coefficient, log σ√Δt) are computed outside and live in SMEM.  The
log-prob reduction (padding lanes masked out) happens in-register and is
written once per row into a lane-dense ``(1, 128)`` block of a
``(B, 1, 128)`` output, of which the wrapper keeps lane 0.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LOG2PI = math.log(2.0 * math.pi)
LANES = 128


def _sde_kernel(v_ref, x_ref, eps_ref, s_ref, xn_ref, lp_ref, *,
                feat: int):
    # per-call scalars, computed by XLA in the wrapper with the ops of the
    # jnp path: the update recomputes this log-prob there
    # (FlowSDEScheduler.logprob), and the GRPO ratio compares the two
    t, delta, std, coef, log_std = (s_ref[i] for i in range(5))
    v = v_ref[...].astype(F32)
    x = x_ref[...].astype(F32)
    eps = eps_ref[...].astype(F32)

    drift = v + coef * (x + (1.0 - t) * v)
    mean = x - drift * delta
    x_next = mean + std * eps
    xn_ref[...] = x_next.astype(xn_ref.dtype)
    # z = (x_next-mean)/std = eps exactly -> fused logpdf
    lp = -0.5 * (eps * eps + LOG2PI) - log_std
    rows = v.shape[0]
    if rows * LANES != feat:             # static: mask the zero padding
        idx = (jax.lax.broadcasted_iota(jnp.int32, lp.shape, 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, lp.shape, 1))
        lp = jnp.where(idx < feat, lp, 0.0)
    lp_ref[...] = jnp.full(lp_ref.shape, jnp.sum(lp), F32)


@functools.partial(jax.jit, static_argnames=("eta", "interpret"))
def sde_step(v: jax.Array, x: jax.Array, eps: jax.Array, t: jax.Array,
             t_next: jax.Array, *, eta: float = 0.7,
             interpret: bool = False):
    """v, x, eps: (B, ...); t/t_next scalar f32. Returns (x_next, logp (B,))."""
    B = x.shape[0]
    feat = int(x.size // B)
    rows = -(-feat // LANES)
    pad = rows * LANES - feat

    def tile(a):
        a = a.reshape(B, feat)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)))
        return a.reshape(B, rows, LANES)

    t = jnp.asarray(t, F32)
    # σ argument clamped (FlowSDEScheduler.t_sigma_max); drift uses raw t —
    # identical numerics to the jnp scheduler path (asserted in tests)
    tc = jnp.clip(t, 1e-4, 0.96)
    sigma = eta * jnp.sqrt(tc / (1.0 - tc))
    delta = t - jnp.asarray(t_next, F32)
    std = sigma * jnp.sqrt(delta)
    scalars = jnp.stack([t, delta, std, sigma ** 2 / (2.0 * t),
                         jnp.log(std)])
    row = pl.BlockSpec((pl.squeezed, rows, LANES), lambda b: (b, 0, 0))
    kernel = functools.partial(_sde_kernel, feat=feat)
    x_next, logp = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[row, row, row,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[
            row,
            pl.BlockSpec((pl.squeezed, 1, LANES), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, rows, LANES), F32),
            jax.ShapeDtypeStruct((B, 1, LANES), F32),
        ],
        interpret=interpret,
        name="sde_step",
    )(tile(v), tile(x), tile(eps), scalars)
    x_next = x_next.reshape(B, rows * LANES)[:, :feat]
    return x_next.reshape(x.shape), logp[:, 0, 0]
