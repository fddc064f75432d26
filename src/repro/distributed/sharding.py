"""Sharded jit entry points for the trainer's sample / rewards / update,
and the :class:`PartitionPlan` mapping params to mesh layouts.

Layout: every batch-major array (trajectories, rewards, advantages,
condition embeddings) is sharded over the mesh "data" axis on its batch
dimension.  Parameters and AdamW moments are laid out per the
:class:`PartitionPlan` — replicated when ``model_parallel=1`` (pure data
parallelism, bit-identical to the historical 1-D path), or sharded along
the "model" axis otherwise: FSDP-style for dense backbone leaves, expert-
parallel for MoE tables, head-parallel for attention/MLA projections, as
declared by the per-module logical axes in ``repro.models.params``
(:data:`repro.models.params.MODEL_SHARDABLE` orders the priorities).  All
entry points are ``jax.jit`` with explicit ``in_shardings`` /
``out_shardings``; XLA's SPMD partitioner inserts the collectives (grad
all-reduce over "data", the gather / reduce-scatter pair around sharded
params over "model"), which keeps the math bit-comparable with the
single-device path up to floating-point reduction order.

``Trajectory`` batch-axis positions: ``xs`` (T+1, B, ...) and ``logps``
(T, B) carry batch on axis 1; ``cond`` on axis 0; ``ts``/``sde_mask`` are
replicated schedule arrays.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.rollout import Trajectory
from repro.distributed.mesh import DATA_AXIS, MODEL_AXIS, mesh_dp, mesh_mp
from repro.models import params as params_lib


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, axis: int = 0) -> NamedSharding:
    """Shard dimension ``axis`` over the data axis (batch-major layout)."""
    return NamedSharding(mesh, PartitionSpec(*([None] * axis + [DATA_AXIS])))


def traj_shardings(mesh: Mesh) -> Trajectory:
    """Per-field shardings of a grouped Trajectory."""
    return Trajectory(
        xs=batch_sharding(mesh, 1),
        logps=batch_sharding(mesh, 1),
        ts=replicated(mesh),
        sde_mask=replicated(mesh),
        cond=batch_sharding(mesh, 0),
    )


# --------------------------------------------------------------------- plan

def _key_name(k) -> str:
    # DictKey -> .key, SequenceKey -> .idx, GetAttrKey (NamedTuples such as
    # RLState/AdamWState) -> .name
    for attr in ("key", "idx", "name"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def _path_names(path) -> tuple:
    return tuple(_key_name(k) for k in path)


class PartitionPlan:
    """Maps every param pytree leaf — and any state leaf mirroring one,
    i.e. the AdamW moments — to a :class:`NamedSharding` on the train mesh.

    Data-driven: built from the model's param *spec* tree (the same
    :class:`repro.models.params.P` leaves that carry shapes and
    initializers), so the plan can never drift from the parameter
    structure and no module-name ``if`` ladder exists anywhere.  Each leaf
    shards at most one dim over the "model" axis, chosen by
    :func:`repro.models.params.model_shard_dim`; everything else (and the
    whole plan when ``model_parallel=1``) is replicated, which makes the
    ``mp=1`` jit layouts identical to the historical replicated path.

    Layouts are a *runtime* choice: checkpoints save/restore through the
    canonical unsharded layout (``jax.device_get`` gathers on save), so a
    state written under one plan restores under any other via
    ``jax.device_put(state, plan.state_shardings(state))``.
    """

    def __init__(self, mesh: Mesh, spec):
        self.mesh = mesh
        self.spec = spec
        self.model_parallel = mesh_mp(mesh)
        self._param_shardings = None

    def param_specs(self):
        """Pytree (matching the param structure) of PartitionSpecs."""
        mp = self.model_parallel

        def one(p):
            dim = params_lib.model_shard_dim(p.shape, p.axes, mp)
            if dim is None:
                return PartitionSpec()
            entries = [None] * len(p.shape)
            entries[dim] = MODEL_AXIS
            return PartitionSpec(*entries)

        return jax.tree.map(one, self.spec, is_leaf=params_lib._is_p)

    def param_shardings(self):
        """Pytree (matching the param structure) of NamedShardings."""
        if self._param_shardings is None:
            self._param_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.param_specs(),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
        return self._param_shardings

    def _table(self):
        """[(param path names, shape, sharding)] for suffix matching."""
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.spec, is_leaf=params_lib._is_p)
        shardings = jax.tree.leaves(
            self.param_shardings(),
            is_leaf=lambda x: isinstance(x, NamedSharding))
        return [(_path_names(path), tuple(p.shape), sh)
                for (path, p), sh in zip(flat, shardings)]

    def state_shardings(self, state):
        """Sharding pytree for a full train state (``RLState``): each state
        leaf whose pytree path ends with a param's path — the AdamW ``mu`` /
        ``nu`` moments are ``tree.map`` images of params, so their subtree
        paths match exactly — inherits that param's sharding (the FSDP
        contract: moments shard with their param); everything else (step
        counters, scalars) is replicated.  Structural, not name-based: no
        optimizer-specific knowledge lives here."""
        rep = replicated(self.mesh)
        table = self._table()

        def one(path, leaf):
            names = _path_names(path)
            shape = tuple(jnp.shape(leaf))
            best = None
            for pnames, pshape, sh in table:
                if (pshape == shape and len(pnames) <= len(names)
                        and names[len(names) - len(pnames):] == pnames):
                    if best is None or len(pnames) > len(best[0]):
                        best = (pnames, sh)
            return best[1] if best is not None else rep

        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        return jax.tree_util.tree_unflatten(
            treedef, [one(p, leaf) for p, leaf in flat])

    def bytes_report(self, state) -> Dict[str, int]:
        """Host-side byte accounting under this plan: the canonical
        (unsharded) total vs what one device actually holds — the FSDP win
        ``perf.log_memory`` surfaces.  Equal when nothing is sharded."""
        shardings = jax.tree.leaves(
            self.state_shardings(state),
            is_leaf=lambda x: isinstance(x, NamedSharding))
        total = per_dev = sharded = 0
        for leaf, sh in zip(jax.tree.leaves(state), shardings):
            size = 1
            for d in jnp.shape(leaf):
                size *= int(d)
            nbytes = size * jnp.dtype(jnp.result_type(leaf)).itemsize
            denom = 1
            for entry in sh.spec:
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, tuple) else (entry,)):
                    denom *= int(self.mesh.shape[ax])
            total += nbytes
            per_dev += nbytes // denom
            sharded += denom > 1
        return {"total_bytes": int(total), "per_device_bytes": int(per_dev),
                "sharded_leaves": int(sharded)}


def partition_plan(mesh: Optional[Mesh], spec) -> Optional[PartitionPlan]:
    """The PartitionPlan for ``mesh`` over a model's param ``spec`` tree
    (None for the single-device no-mesh path)."""
    if mesh is None:
        return None
    return PartitionPlan(mesh, spec)


# --------------------------------------------------------------- validation

def check_batch_divisible(batch: int, mesh: Optional[Mesh],
                          microbatch: int = 0) -> None:
    """Clear trace-time errors instead of opaque reshard/pad behavior."""
    if microbatch and microbatch > 1 and batch % microbatch != 0:
        raise ValueError(
            f"batch size {batch} is not divisible by dist.microbatch="
            f"{microbatch}; pick a microbatch count that divides "
            f"num_prompts × group_size")
    per_chunk = batch // microbatch if microbatch and microbatch > 1 else batch
    dp = mesh_dp(mesh)
    if dp > 1 and per_chunk % dp != 0:
        raise ValueError(
            f"per-update batch {per_chunk} (batch {batch}"
            + (f" / microbatch {microbatch}" if microbatch > 1 else "")
            + f") is not divisible by the mesh data axis ({dp} devices); "
            "adjust num_prompts/group_size so every device gets equal work")


# ------------------------------------------------------------- jit wrappers

def _on_mesh(fn: Callable, mesh: Mesh) -> Callable:
    """``fn`` traced with ``mesh`` as the abstract mesh in context, so the
    Pallas kernel wrappers of ``repro.kernels.ops`` can split their batch
    over its data axis with ``shard_map`` (the TPU compiler cannot
    partition a Mosaic kernel by itself)."""
    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)
    return traced


def _plan_jit(fn: Callable, in_shardings, out_shardings=None):
    """Shared constructor for the non-donating sharded entry points.  The
    donating wrappers (``jit_update``/``jit_fused_step``) call ``jax.jit``
    directly instead, so the jaxlint scope graph keys their donation
    tracking off the literal ``donate_argnums`` keyword (R005); this helper
    is reached through the linter's *transitive* wrapper detection."""
    kw: Dict[str, Any] = {}
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    return jax.jit(fn, in_shardings=in_shardings, **kw)


def jit_sample(fn: Callable, mesh: Optional[Mesh], params_sharding=None):
    """``fn(params, cond, key, sde_mask) -> Trajectory`` — key/mask
    replicated, cond and the returned trajectory batch-sharded, params laid
    out per the PartitionPlan (``params_sharding`` — None replicates, the
    ``mp=1`` layout)."""
    if mesh is None:
        return jax.jit(fn)
    rep = replicated(mesh)
    psh = params_sharding if params_sharding is not None else rep
    return _plan_jit(_on_mesh(fn, mesh),
                     (psh, batch_sharding(mesh, 0), rep, rep),
                     traj_shardings(mesh))


def jit_rewards(fn: Callable, mesh: Optional[Mesh], *,
                with_params: bool = False):
    """``fn(x0, cond_meta[, reward_params]) -> (rewards, adv, stats)`` —
    batch-major inputs and outputs sharded over the data axis (the stats
    dict is scalar reductions, replicated by construction).
    ``with_params`` (``perf.offload_rewards``) accepts the host-offloaded
    reward-tower store as a third, replicated argument."""
    if mesh is None:
        return jax.jit(fn)
    b0 = batch_sharding(mesh, 0)
    if with_params:
        return _plan_jit(fn, (b0, b0, replicated(mesh)))
    return _plan_jit(fn, (b0, b0))


def jit_fused_step(fn: Callable, mesh: Optional[Mesh], state_sharding=None,
                   *, donate: bool = True, extras_sharding=None,
                   with_reward_params: bool = False):
    """``fn(state, cond_g, key, it, sde_mask, extras[, reward_params]) ->
    (state, metrics)`` — the ``repro.perf`` fused train step: RLState
    donated and laid out per the PartitionPlan (``state_sharding`` — None
    replicates), the group-repeated cond batch sharded over the data axis
    (the trajectory it becomes inside never crosses a jit boundary, so XLA
    propagates the batch sharding through rollout → rewards → update and
    inserts the same collectives the unfused path gets).  Donation
    rewrites the state in place per shard: in- and out-shardings are the
    same pytree.  ``extras_sharding`` lays out the ``update_extras()``
    tuple — None replicates; NFT's ref_params alias the placed params, so
    they arrive model-sharded under mp>1 and must be accepted in that
    layout.  ``with_reward_params`` (``perf.offload_rewards``) appends the
    host-offloaded reward-tower store as a trailing replicated argument."""
    donate_argnums = (0,) if donate else ()
    if mesh is None:
        return jax.jit(fn, donate_argnums=donate_argnums)
    rep = replicated(mesh)
    ssh = state_sharding if state_sharding is not None else rep
    esh = extras_sharding if extras_sharding is not None else rep
    in_sh = [ssh, batch_sharding(mesh, 0), rep, rep, rep, esh]
    if with_reward_params:
        in_sh.append(rep)
    return jax.jit(
        _on_mesh(fn, mesh),
        in_shardings=tuple(in_sh),
        out_shardings=(ssh, rep),
        donate_argnums=donate_argnums)


def jit_update(fn: Callable, mesh: Optional[Mesh], state_sharding=None, *,
               donate: bool = True, extras_sharding=None):
    """``fn(state, traj, adv, key, extras) -> (state, metrics)`` — RLState
    donated and laid out per the PartitionPlan (``state_sharding`` — None
    replicates; params + AdamW moments rewritten in place per shard),
    trajectory/advantages batch-sharded; XLA all-reduces the grads over
    "data" and gathers/reduce-scatters sharded params over "model".
    ``extras_sharding`` lays out the ``update_extras()`` tuple — None
    replicates (see :func:`jit_fused_step`)."""
    donate_argnums = (0,) if donate else ()
    if mesh is None:
        return jax.jit(fn, donate_argnums=donate_argnums)
    rep = replicated(mesh)
    ssh = state_sharding if state_sharding is not None else rep
    esh = extras_sharding if extras_sharding is not None else rep
    return jax.jit(
        _on_mesh(fn, mesh),
        in_shardings=(ssh, traj_shardings(mesh), batch_sharding(mesh, 0),
                      rep, esh),
        out_shardings=(ssh, rep),
        donate_argnums=donate_argnums)
