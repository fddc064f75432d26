"""Kernel dispatch layer.

The model/trainer code calls these wrappers; they route to the Pallas kernel
on TPU (or in interpret mode when REPRO_PALLAS=interpret — the CPU CI
configuration) and to the pure-jnp reference otherwise.  This keeps the
XLA-path HLO (what the CPU dry-run lowers) and the kernel path behaviourally
identical — the tests assert exactly that.

On a TPU backend the compiled kernels always run: ``REPRO_PALLAS=off`` or
``interpret`` there raises instead of quietly running the reference or the
interpreter under the name of a chip run.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.mesh import DATA_AXIS
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.grpo_loss import grpo_loss as _grpo
from repro.kernels.sde_step import sde_step as _sde
from repro.kernels.ssd_scan import ssd_scan as _ssd


def _mode() -> str:
    env = os.environ.get("REPRO_PALLAS", "auto")
    on_tpu = jax.default_backend() == "tpu"
    if env in ("interpret", "off") and on_tpu:
        raise RuntimeError(
            f"REPRO_PALLAS={env} is a CPU-only setting; on a TPU backend the "
            "compiled Pallas kernels run — unset REPRO_PALLAS")
    if env in ("interpret", "off", "on"):
        return env
    return "on" if on_tpu else "off"


def pallas_enabled() -> bool:
    return _mode() in ("on", "interpret")


def _interpret() -> bool:
    return _mode() == "interpret"


def _batch_parallel(kernel, n_batched, *args):
    """``kernel(*args)``, the first ``n_batched`` args split on their
    leading (batch) axis over the data axis of the mesh being traced under
    (``repro.distributed`` sets it), each device running the kernel on its
    rows; the other args are replicated.  The TPU compiler cannot
    partition a Mosaic kernel by itself.  Without such a mesh, or inside a
    ``shard_map`` that already owns the data axis, the call is direct."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh.empty or DATA_AXIS not in mesh.axis_names
            or DATA_AXIS in mesh.manual_axes
            or mesh.shape[DATA_AXIS] == 1):
        return kernel(*args)
    specs = tuple(P(DATA_AXIS) if i < n_batched else P()
                  for i in range(len(args)))
    return jax.shard_map(kernel, mesh=mesh, in_specs=specs,
                         out_specs=P(DATA_AXIS), check_vma=False)(
        *(jnp.asarray(a) for a in args))


def flash_attention(q, k, v, *, causal=True, window=0):
    if pallas_enabled():
        return _flash(q, k, v, causal=causal, window=window,
                      interpret=_interpret())
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def ssd_scan(x, dt, a, bm, cm, *, chunk=128):
    if pallas_enabled():
        return _ssd(x, dt, a, bm, cm, chunk=chunk, interpret=_interpret())
    return ref.ssd_scan_ref(x, dt, a, bm, cm)


def sde_step(v, x, eps, t, t_next, *, eta=0.7):
    if pallas_enabled():
        kernel = functools.partial(_sde, eta=eta, interpret=_interpret())
        return _batch_parallel(kernel, 3, v, x, eps, t, t_next)
    return ref.sde_step_ref(v, x, t, t_next, eps, eta=eta)


def grpo_loss(logp_new, logp_old, adv, ratio_mean=None, *, clip=0.2,
              guard=False):
    if pallas_enabled():
        kernel = functools.partial(_grpo, clip=clip, guard=guard,
                                   interpret=_interpret())
        extra = () if ratio_mean is None else (ratio_mean,)
        return _batch_parallel(kernel, 3, logp_new, logp_old, adv, *extra)
    return ref.grpo_loss_ref(logp_new, logp_old, adv, clip=clip, guard=guard)


def grpo_loss_trainable(logp_new, logp_old, adv, *, clip=0.2):
    """Differentiable GRPO loss for the trainer: fused-kernel forward with
    the closed-form PPO-clip VJP (see kernels/grpo_loss.py); clip-fraction
    metric computed alongside (non-differentiated)."""
    if pallas_enabled():
        from repro.kernels.grpo_loss import grpo_loss_diff
        kernel = functools.partial(grpo_loss_diff, clip=clip,
                                   interpret=_interpret())
        loss = _batch_parallel(kernel, 3, logp_new, logp_old, adv)
        ratio = jnp.exp(jnp.clip(jax.lax.stop_gradient(logp_new - logp_old),
                                 -20.0, 20.0))
        frac = (jnp.abs(ratio - 1.0) > clip).astype(jnp.float32)
        return loss, frac
    return ref.grpo_loss_ref(logp_new, logp_old, adv, clip=clip, guard=False)
