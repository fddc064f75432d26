"""``shard_map`` entry point: per-device independent rollouts.

The jit-with-sharding path (``sharding.jit_sample``) keeps multi-device
sampling numerically identical to single-device — the right tool for
training.  For pure *generation throughput* (filling a reward buffer,
serving bursts) cross-layout bit-equality is irrelevant; this entry point
instead hands each data shard its own fold of the PRNG key and runs the
rollout fully locally — zero cross-device communication, embarrassingly
parallel.  Consequently the samples differ from (are statistically
exchangeable with, not equal to) a single-device rollout of the same key.

On a 2-D ``(data, model)`` mesh the shard_map paths mention only the
"data" axis: params arrive replicated (gathered) and every model column
computes the same shard — correct, but it forgoes the PartitionPlan's
memory win.  The serving executor (``make_rollout_keyed_sharded``)
therefore switches to a plan-consuming SPMD jit when ``mp > 1``.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.rollout import Trajectory, rollout, rollout_keyed
from repro.distributed.mesh import DATA_AXIS, mesh_dp, mesh_mp
from repro.distributed.sharding import (batch_sharding, replicated,
                                        traj_shardings)


def make_rollout_sharded(adapter, scheduler, num_steps: int, mesh: Mesh,
                         sde_mask=None):
    """Build the jitted per-shard rollout ONCE; returns
    ``fn(params, cond, key) -> Trajectory``.  Reuse the returned callable
    across calls (a generation loop) — rebuilding it per batch re-traces
    the whole rollout every time."""

    def local(params, cond_shard, key):
        k = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))
        return rollout(adapter, params, cond_shard, k, scheduler, num_steps,
                       sde_mask)

    out_specs = Trajectory(xs=P(None, DATA_AXIS), logps=P(None, DATA_AXIS),
                           ts=P(), sde_mask=P(), cond=P(DATA_AXIS))
    # check_vma=False: ts/sde_mask are replicated by construction (identical
    # computation per shard) but shard_map cannot prove it
    sharded = jax.shard_map(local, mesh=mesh,
                            in_specs=(P(), P(DATA_AXIS), P()),
                            out_specs=out_specs, check_vma=False)
    dp = mesh_dp(mesh)

    def run(params, cond: jax.Array, key: jax.Array) -> Trajectory:
        if cond.shape[0] % dp != 0:
            raise ValueError(
                f"rollout batch {cond.shape[0]} is not divisible by the "
                f"data axis ({dp} devices)")
        return _jitted(params, cond, key)

    _jitted = jax.jit(sharded)
    return run


def make_rollout_keyed_sharded(adapter, scheduler, num_steps: int,
                               mesh: Optional[Mesh], x0_only: bool = False,
                               plan=None):
    """Sharded entry point for the *per-request-keyed* rollout (the serving
    engine's executor): cond AND the (B, 2) per-request key batch are both
    sharded over the data axis.

    On a data-only mesh (``mp=1``) this is a ``shard_map``: each device
    runs exactly the computation the single-device path runs for its slice
    of requests — no axis-index key folding, hence **bit-identical per
    request** to ``mesh=None`` (tests/test_serving.py asserts exact
    equality on 4 faked host devices).  With ``mp > 1`` the executor is
    instead an SPMD jit consuming the PartitionPlan — params stay
    model-sharded (the memory point of the plan) and XLA inserts the
    gather collectives, so results are f32-rounding-equal (reduction
    order), not bit-identical, to the ``mp=1`` layouts.

    Returns ``fn(params, cond, keys, sde_mask) -> Trajectory`` (jitted;
    build once per (batch, num_steps) shape and reuse — the engine's
    compile cache does exactly that).  Batch must divide the mesh's data
    axis; the engine's bucket grid is dp-aligned to guarantee it.

    ``x0_only=True`` returns just the final latents (B, Lt, ld) — the
    serving queue's executor: XLA then dead-code-eliminates the stacked
    per-step trajectory/log-prob buffers the scan would otherwise
    materialize (x0 values are bit-identical either way)."""

    def local(params, cond_shard, keys_shard, sde_mask):
        traj = rollout_keyed(adapter, params, cond_shard, keys_shard,
                             scheduler, num_steps, sde_mask)
        return traj.x0 if x0_only else traj

    if mesh is None:
        return jax.jit(local)
    dp = mesh_dp(mesh)
    if mesh_mp(mesh) > 1:
        rep = replicated(mesh)
        psh = plan.param_shardings() if plan is not None else rep
        b0 = batch_sharding(mesh, 0)
        out_sh = b0 if x0_only else traj_shardings(mesh)
        _jitted = jax.jit(local, in_shardings=(psh, b0, b0, rep),
                          out_shardings=out_sh)
    else:
        out_specs = (P(DATA_AXIS) if x0_only else
                     Trajectory(xs=P(None, DATA_AXIS),
                                logps=P(None, DATA_AXIS),
                                ts=P(), sde_mask=P(), cond=P(DATA_AXIS)))
        # check_vma=False: ts/sde_mask are replicated by construction
        # (identical computation per shard) but shard_map cannot prove it
        sharded = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=out_specs, check_vma=False)
        _jitted = jax.jit(sharded)

    def run(params, cond, keys, sde_mask):
        if cond.shape[0] % dp != 0:
            raise ValueError(
                f"keyed rollout batch {cond.shape[0]} is not divisible by "
                f"the data axis ({dp} devices) — bucket sizes must be "
                "dp-aligned")
        return _jitted(params, cond, keys, sde_mask)

    return run


def rollout_sharded(adapter, params, cond: jax.Array, key: jax.Array,
                    scheduler, num_steps: int, mesh: Optional[Mesh],
                    sde_mask=None) -> Trajectory:
    """One-shot convenience over ``make_rollout_sharded`` (falls back to the
    plain rollout when no mesh is given).  In a loop, build the callable
    once with the factory instead — this wrapper re-traces per call."""
    if mesh is None:
        return rollout(adapter, params, cond, key, scheduler, num_steps,
                       sde_mask)
    return make_rollout_sharded(adapter, scheduler, num_steps, mesh,
                                sde_mask)(params, cond, key)
