"""SDE schedulers (paper Table 1) behind a unified ``SDESchedulerMixin``.

Rectified-flow convention: ``x_t = (1-t)·x₀ + t·ε``, velocity target
``u = ε − x₀``; sampling integrates t from 1 (noise) down to 0 (data).
Writing ``Δ = t - t_next > 0`` for a step, the paper's Eq. 1 becomes

    x_next = x_t − [v + (σ_t²/2t)(x_t + (1−t)·v)]·Δ + σ_t·√Δ·ε

which is a Gaussian transition — its log-probability (required by GRPO's
policy-gradient ratio) is computed in closed form by ``logprob``.

Dynamics (select via ``sde_type`` — one config knob, paper §3.1):
  flow_sde   σ_t = η·√(t/(1−t))          (Flow-GRPO)
  dance_sde  σ_t = η                      (DanceGRPO)
  cps        coefficient-preserving noise  (FlowCPS; see class docstring)
  ode        σ_t = 0                      (deterministic; NFT/AWM)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import registry

F32 = jnp.float32
_EPS = 1e-4
# a host constant: jnp.log(2π) evaluated on a v5e is off by 3.4e-5, which
# the per-element log-density sum over a 64 K-float latent turns into a 1.1
# shift of every log-prob (and of the GRPO ratio against the fused kernel)
LOG2PI = math.log(2.0 * math.pi)


def _sum_dims(x: jax.Array) -> jax.Array:
    """Sum over all but the leading (batch) axis."""
    return x.reshape(x.shape[0], -1).sum(axis=-1)


def gaussian_logpdf(x: jax.Array, mean: jax.Array, std: jax.Array
                    ) -> jax.Array:
    """Per-sample (batch,) log N(x; mean, std²·I), summed over event dims."""
    z = (x.astype(F32) - mean.astype(F32)) / std
    return _sum_dims(-0.5 * (z * z + LOG2PI) - jnp.log(std)
                     * jnp.ones_like(z))


class SDESchedulerMixin:
    """Unified stochastic-sampling interface (paper §2.1 component type)."""

    eta: float

    def timesteps(self, num_steps: int) -> jax.Array:
        """Descending grid t_0=1-ε … t_T=ε, shape (num_steps+1,)."""
        return jnp.linspace(1.0 - _EPS, _EPS, num_steps + 1, dtype=F32)

    # -- per-dynamics hooks ------------------------------------------------
    def sigma(self, t: jax.Array, t_next: jax.Array) -> jax.Array:
        raise NotImplementedError

    def mean_next(self, v: jax.Array, x: jax.Array, t: jax.Array,
                  t_next: jax.Array) -> jax.Array:
        """Deterministic part of the transition (paper Eq. 1 drift)."""
        delta = t - t_next
        sig = self.sigma(t, t_next)
        drift = v + (sig ** 2 / (2.0 * t)) * (x + (1.0 - t) * v)
        return x - drift * delta

    def noise_std(self, t: jax.Array, t_next: jax.Array) -> jax.Array:
        delta = t - t_next
        return self.sigma(t, t_next) * jnp.sqrt(delta)

    # -- unified API ---------------------------------------------------------
    def step_with_eps(self, v: jax.Array, x: jax.Array, t: jax.Array,
                      t_next: jax.Array, eps: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
        """One sampling step from externally supplied noise ``eps`` (the
        keyed rollout draws per-request noise; ``step`` draws from a batch
        key).  Returns (x_next, logp (batch,)).  Subclasses with a fused
        kernel override THIS hook, so both rollout flavors dispatch to it."""
        xf, vf = x.astype(F32), v.astype(F32)
        mean = self.mean_next(vf, xf, t, t_next)
        std = self.noise_std(t, t_next)
        stochastic = std > 0
        x_next = jnp.where(stochastic, mean + std * eps.astype(F32), mean)
        safe_std = jnp.maximum(std, 1e-20)
        logp = jnp.where(stochastic,
                         gaussian_logpdf(x_next, mean, safe_std),
                         jnp.zeros(x.shape[0], F32))
        return x_next, logp

    def step(self, v: jax.Array, x: jax.Array, t: jax.Array,
             t_next: jax.Array, key: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
        """One sampling step. Returns (x_next, logp (batch,))."""
        eps = jax.random.normal(key, x.shape, F32)
        return self.step_with_eps(v, x, t, t_next, eps)

    def logprob(self, v: jax.Array, x: jax.Array, t: jax.Array,
                t_next: jax.Array, x_next: jax.Array) -> jax.Array:
        """log p(x_next | x; v) — recomputed under *current* params for the
        GRPO importance ratio."""
        xf, vf = x.astype(F32), v.astype(F32)
        mean = self.mean_next(vf, xf, t, t_next)
        std = jnp.maximum(self.noise_std(t, t_next), 1e-20)
        return gaussian_logpdf(x_next, mean, std)

    def step_ode(self, v: jax.Array, x: jax.Array, t: jax.Array,
                 t_next: jax.Array) -> jax.Array:
        """Deterministic flow update (used by MixGRPO's ODE segments and by
        the solver-agnostic algorithms)."""
        return x.astype(F32) - v.astype(F32) * (t - t_next)


@registry.register("scheduler", "flow_sde")
@dataclasses.dataclass
class FlowSDEScheduler(SDESchedulerMixin):
    """Flow-GRPO (Liu et al., 2025): σ_t = η·√(t/(1−t)).

    ``t_sigma_max``: σ diverges at t→1; reference implementations shift the
    timestep grid away from 1, which we reproduce by clamping the σ argument
    (documented deviation, DESIGN.md §8).

    ``step_with_eps`` dispatches to the fused Pallas ``sde_step`` kernel on
    TPU (drift + noise + log-density in one VMEM pass) for BOTH the batch-
    keyed ``step`` and the per-request-keyed serving rollout; the jnp path
    is bit-compatible (tests/test_kernels.py)."""
    eta: float = 0.7
    t_sigma_max: float = 0.96

    def sigma(self, t, t_next):
        tc = jnp.clip(t, _EPS, self.t_sigma_max)
        return self.eta * jnp.sqrt(tc / (1.0 - tc))

    def step_with_eps(self, v, x, t, t_next, eps):
        from repro.kernels import ops
        if ops.pallas_enabled():
            return ops.sde_step(v, x, eps, t, t_next, eta=self.eta)
        return super().step_with_eps(v, x, t, t_next, eps)


@registry.register("scheduler", "dance_sde")
@dataclasses.dataclass
class DanceSDEScheduler(SDESchedulerMixin):
    """DanceGRPO (Xue et al., 2025b): σ_t = η (constant)."""
    eta: float = 0.3

    def sigma(self, t, t_next):
        return jnp.full_like(jnp.asarray(t, F32), self.eta)


@registry.register("scheduler", "cps")
@dataclasses.dataclass
class CPSScheduler(SDESchedulerMixin):
    """FlowCPS (Wang & Yu, 2025) — coefficients-preserving sampling.

    Interpretation implemented (documented deviation, DESIGN.md §8): under the
    rectified flow the noise component of the marginal at time s has std s.
    CPS *rotates* that component instead of adding variance: with
    x̂₀ = x − t·v and ε̂ = (x_ode − (1−t')·x̂₀)/t',

        x_next = (1−t')·x̂₀ + t'·(cos(ηπ/2)·ε̂ + sin(ηπ/2)·ε_fresh)

    so the marginal coefficients ((1−t'), t') of the ODE path are preserved
    exactly while injecting noise σ_t = t'·sin(ηπ/2) — matching Table 1's
    recurrence σ_t = σ_{t−1}·sin(ηπ/2) with σ_{t−1} the carried noise scale.
    """
    eta: float = 0.5

    def sigma(self, t, t_next):
        # reported noise scale: σ = t'·sin(ηπ/2) / sqrt(Δ) so noise_std = σ√Δ
        delta = jnp.maximum(t - t_next, 1e-20)
        return t_next * jnp.sin(self.eta * jnp.pi / 2.0) / jnp.sqrt(delta)

    def mean_next(self, v, x, t, t_next):
        c = jnp.cos(self.eta * jnp.pi / 2.0)
        x0_hat = x - t * v
        x_ode = x - v * (t - t_next)
        eps_hat = (x_ode - (1.0 - t_next) * x0_hat) / jnp.maximum(t_next, _EPS)
        return (1.0 - t_next) * x0_hat + t_next * c * eps_hat

    def noise_std(self, t, t_next):
        return t_next * jnp.sin(self.eta * jnp.pi / 2.0)


@registry.register("scheduler", "ode")
@dataclasses.dataclass
class ODEScheduler(SDESchedulerMixin):
    """Deterministic sampling (σ=0) — for DiffusionNFT / AWM (paper §3.2)."""
    eta: float = 0.0

    def sigma(self, t, t_next):
        return jnp.zeros_like(jnp.asarray(t, F32))


def build(sde_type: str, eta: float) -> SDESchedulerMixin:
    return registry.build("scheduler", sde_type, eta=eta)
