"""Peak-memory introspection for the train step via XLA's
``compiled.memory_analysis()``.

The remat policy trades recompute for activation memory; this module makes
the trade observable without running anything — the update is AOT-lowered
on ``ShapeDtypeStruct``s and compiled, and the analysis byte counts are
returned (``temp`` is the interesting one: scratch + activation buffers,
where the loss backward's per-step residuals live).  Used by
``BaseTrainer.memory_stats``, the ``perf.log_memory`` launcher line and
the tests/test_perf.py regression that peak temp bytes strictly drop
under ``remat="scan"``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.rollout import Trajectory

F32 = jnp.float32

_FIELDS = {
    "temp_bytes": "temp_size_in_bytes",
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "peak_bytes": "peak_memory_in_bytes",
    "generated_code_bytes": "generated_code_size_in_bytes",
}


def analysis_dict(compiled) -> Dict[str, Optional[int]]:
    """``memory_analysis()`` as a plain dict (None where the backend does
    not implement a field — CPU reports temp/argument/output).  A backend
    that returns no analysis at all raises: a missing number must not pass
    for a measured one."""
    mem = compiled.memory_analysis()
    if mem is None:
        raise RuntimeError(f"{jax.default_backend()} returned no "
                           "memory_analysis for the compiled step")
    return {k: getattr(mem, attr, None) for k, attr in _FIELDS.items()}


def _struct(tree: Any) -> Any:
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def state_bytes(trainer) -> Dict[str, int]:
    """Param + optimizer byte footprint: the canonical (unsharded) total
    and what one device actually holds under the trainer's active
    :class:`repro.distributed.PartitionPlan` — equal when nothing is
    sharded (no mesh, or ``model_parallel=1``), strictly smaller per
    device under an FSDP/expert/head-sharded plan.  Host-side arithmetic
    over shapes; nothing compiles or runs."""
    plan = getattr(trainer, "plan", None)
    if plan is not None:
        return plan.bytes_report(trainer.state)
    total = 0
    for leaf in jax.tree.leaves(trainer.state):
        size = 1
        for d in jnp.shape(leaf):
            size *= int(d)
        total += size * jnp.dtype(jnp.result_type(leaf)).itemsize
    return {"total_bytes": int(total), "per_device_bytes": int(total),
            "sharded_leaves": 0}


def lower_step(trainer, cond: jax.Array) -> Dict[str, Any]:
    """Lower the programs one ``trainer.step`` runs, on shapes, for a
    ``cond`` prompt batch of shape (P, Lc, cond_dim): ``sample``,
    ``rewards`` and ``update``, plus ``fused`` when ``perf.fuse_step`` is
    on.  Returns ``jax.stages.Lowered`` objects; compiling them ahead of
    time fills jit's cache, so the first real step of the same shapes
    reuses those executables instead of compiling again.

    Pure introspection: nothing executes and no live buffer is touched
    (lowering on structs never donates real state)."""
    f = trainer.flow
    P, Lc, D = cond.shape
    B = P * f.group_size
    T = f.num_steps
    traj = Trajectory(
        xs=jax.ShapeDtypeStruct((T + 1, B, f.latent_tokens, f.latent_dim),
                                F32),
        logps=jax.ShapeDtypeStruct((T, B), F32),
        ts=jax.ShapeDtypeStruct((T + 1,), F32),
        sde_mask=jax.ShapeDtypeStruct((T,), jnp.bool_),
        cond=jax.ShapeDtypeStruct((B, Lc, D), F32),
    )
    x0 = jax.ShapeDtypeStruct((B, f.latent_tokens, f.latent_dim), F32)
    adv = jax.ShapeDtypeStruct((B,), F32)
    key = _struct(jax.random.PRNGKey(0))
    state = _struct(trainer.state)
    extras = _struct(trainer.update_extras())
    reward_params = ((_struct(trainer._reward_store_host),)
                     if trainer.offloads_rewards else ())
    out = {
        "sample": trainer._sample_jit.lower(state.params, traj.cond, key,
                                            traj.sde_mask),
        "rewards": trainer._rewards_jit.lower(x0, {"cond": traj.cond},
                                              *reward_params),
        "update": trainer._update_jit.lower(state, traj, adv, key, extras),
    }
    if trainer._fused_jit is not None:
        it = jax.ShapeDtypeStruct((), jnp.int32)
        out["fused"] = trainer._fused_jit.lower(
            state, traj.cond, key, it, traj.sde_mask, extras,
            *reward_params)
    return out


def update_memory(trainer, cond: jax.Array) -> Dict[str, Dict]:
    """AOT-compile the trainer's jitted update — and, when
    ``perf.fuse_step`` is on, the fused step — for a ``cond`` prompt batch
    of shape (P, Lc, cond_dim), and report the analysis byte counts."""
    from repro.perf.offload import reward_tower_report
    lowered = lower_step(trainer, cond)
    out = {"update": analysis_dict(lowered["update"].compile()),
           "state": state_bytes(trainer),
           # the frozen-tower footprint and what perf.offload_rewards frees
           # from the device (host-side shape arithmetic, nothing compiles)
           "reward_towers": reward_tower_report(trainer)}
    if "fused" in lowered:
        out["fused"] = analysis_dict(lowered["fused"].compile())
    return out
