"""BaseTrainer — the paper's algorithm-logic component type.

Owns: sampling (rollout), reward computation (MultiRewardLoader), advantage
aggregation, and the optimization step.  Subclasses implement ``loss_fn``
(and may override ``sde_mask`` / ``wants_sde``); everything else — including
distribution, preprocessing and multi-reward handling — is shared, which is
exactly the O(M+N) decoupling the paper claims.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import distributed, optim, perf as perf_lib, registry
from repro.config import (ArchConfig, DistConfig, FlowRLConfig, OptimConfig,
                          PerfConfig, RewardSpec)
from repro.core import schedulers
from repro.core.rewards import MultiRewardLoader, compute_advantages
from repro.core.rollout import Trajectory, group_repeat, rollout
from repro.models import params as params_lib
from repro.models.flow import FlowAdapter

F32 = jnp.float32

# default reward is shape-agnostic (works for any latent geometry)
DEFAULT_REWARDS = (RewardSpec(reward_type="latent_norm", weight=1.0),)


class RLState(NamedTuple):
    params: Any
    opt: optim.AdamWState


class BaseTrainer:
    """Subclass contract: implement ``loss_fn(params, traj, adv, key)``
    (plus one trailing argument per pytree returned by ``update_extras``)."""

    #: scheduler used for rollouts; GRPO variants need an SDE, NFT/AWM
    #: override to force ODE sampling (solver-agnostic algorithms)
    rollout_sde: bool = True

    #: subclasses whose loss reads buffers aliasing RLState (e.g. NFT's
    #: reference policy) must opt out of update-buffer donation
    donate_state_ok: bool = True

    #: subclasses whose loss computes batch-GLOBAL statistics (e.g.
    #: GRPO-Guard's RatioNorm mean) must opt out of gradient-accumulation
    #: microbatching — chunked evaluation would silently turn the statistic
    #: chunk-local and change the training math
    microbatch_safe: bool = True

    def __init__(self, arch_cfg: ArchConfig, flow_cfg: FlowRLConfig,
                 opt_cfg: OptimConfig, *, key: jax.Array,
                 cond_dim: int = 512, dtype=jnp.bfloat16,
                 dist: Optional[DistConfig] = None,
                 perf: Optional[PerfConfig] = None):
        if flow_cfg.group_size < 1:
            raise ValueError(
                f"flow.group_size must be >= 1, got {flow_cfg.group_size}")
        self.cfg = arch_cfg
        self.flow = flow_cfg
        self.opt_cfg = opt_cfg
        self.dist = dist or DistConfig()
        self.perf = perf_lib.validate(perf or PerfConfig())
        # resolved once: the jax.checkpoint offload policy for the scan
        # bodies (None unless perf.remat_offload — plain remat stays the
        # bit-identical program it always was)
        self._remat_policy = perf_lib.remat_policy(self.perf)
        if self.dist.microbatch < 0:
            raise ValueError(
                f"dist.microbatch must be >= 0, got {self.dist.microbatch}")
        if self.dist.microbatch > 1 and not self.microbatch_safe:
            raise ValueError(
                f"{type(self).__name__} computes batch-global loss "
                "statistics and cannot be microbatched: chunked gradient "
                "accumulation would make them chunk-local and change the "
                "training math — set dist.microbatch=0")
        self.mesh = distributed.train_mesh(self.dist)
        self.adapter = FlowAdapter(
            arch_cfg, flow_cfg, cond_dim,
            policy_dtype=perf_lib.resolve_policy_dtype(self.perf))
        # static SDE-branch knowledge for the rollout's dead-branch
        # specialization: pure-ODE trainers (NFT/AWM) never take the SDE
        # branch, trainers that keep the base all-stochastic mask never take
        # the ODE one; only a dynamic mask (MixGRPO) pays for both
        if not self.rollout_sde:
            self.sde_mode = "all_ode"
        elif type(self).sde_mask is BaseTrainer.sde_mask:
            self.sde_mode = "all_sde"
        else:
            self.sde_mode = "mixed"
        sde_type = flow_cfg.sde_type if self.rollout_sde else "ode"
        self.scheduler = schedulers.build(sde_type, flow_cfg.eta)
        k_p, k_r = jax.random.split(key)
        params = params_lib.init(self.adapter.spec(), k_p, dtype)
        self.optimizer = registry.build("optimizer", opt_cfg.optimizer)
        # the PartitionPlan maps every param leaf (and the AdamW moments
        # mirroring it) to a mesh layout — replicated at mp=1, FSDP/expert/
        # head-sharded over "model" otherwise (repro.distributed.sharding)
        self.plan = distributed.partition_plan(self.mesh,
                                               self.adapter.spec())
        self.state = self.place_state(
            RLState(params, self.optimizer.init(params)))
        self.params_sharding = (None if self.plan is None
                                else self.plan.param_shardings())
        self.state_sharding = (None if self.plan is None
                               else self.plan.state_shardings(self.state))
        specs = flow_cfg.rewards or DEFAULT_REWARDS
        self.loader = MultiRewardLoader(specs, k_r)
        # perf.offload_rewards: park the frozen towers in host memory; the
        # rewards/fused jit then takes them as an ARGUMENT (closure capture
        # would bake the trace-time values in as device constants — the
        # PR-2 class, jaxlint R003 — and keep them resident)
        self._reward_store_host = None
        self._reward_prefetch = None
        self._reward_put_sharding = (None if self.mesh is None
                                     else distributed.replicated(self.mesh))
        if self.perf.offload_rewards:
            self._reward_store_host = perf_lib.offload_param_store(
                self.loader)
        self._lr = optim.make_schedule(opt_cfg)
        self._engine = None
        self._sample_jit = distributed.jit_sample(self._sample, self.mesh,
                                                  self.params_sharding)
        self._update_jit = distributed.jit_update(
            self._update, self.mesh, self.state_sharding,
            donate=self.dist.donate_state and self.donate_state_ok,
            extras_sharding=self.update_extras_sharding())
        # keeps ``_rewards``' name, so the program is ``jit__rewards``
        self._rewards_jit = distributed.jit_rewards(
            functools.update_wrapper(
                functools.partial(self._rewards,
                                  group_size=flow_cfg.group_size),
                self._rewards),
            self.mesh, with_params=self.perf.offload_rewards)
        self._fused_jit = (perf_lib.make_fused_step(self)
                           if self.perf.fuse_step else None)

    def place_state(self, state: RLState) -> RLState:
        """Lay a canonical (host/unsharded) RLState out for this trainer's
        mesh per the PartitionPlan — replicated at ``mp=1``, model-sharded
        otherwise; identity on the single-device path.  Used at init and by
        checkpoint restore (``Experiment.train``), which is what makes
        layouts a runtime choice: a checkpoint written under ``dp=4``
        resumes under ``dp=2×mp=2`` by re-placing here."""
        if self.mesh is None:
            return state
        return jax.device_put(state, self.plan.state_shardings(state))

    # ------------------------------------------------------------- sampling
    def attach_engine(self, engine) -> None:
        """Opt online rollouts into a :class:`repro.serving.ServingEngine`
        (usually ``ServingEngine.for_trainer(self)``): sampling then runs
        the per-request-keyed, bucket-padded, compile-cached path the
        serving stack uses — per-sample results independent of batch
        composition and device layout, and one compile cache shared between
        training rollouts and user-facing serving.  The engine must use
        this trainer's adapter/scheduler/num_steps (and mesh, if any);
        pass ``None`` to detach.  A mismatched scheduler would make the
        update's recomputed log-probs a *different* transition density
        than the one sampled under — silently wrong ratios — so the
        components are validated here, not trusted."""
        if engine is not None:
            if self.perf.fuse_step:
                raise ValueError(
                    "perf.fuse_step and an attached serving engine are "
                    "mutually exclusive: the engine's bucketed rollout is "
                    "host-driven and cannot live inside the fused jit — "
                    "set perf.fuse_step=false or detach the engine")
            if engine.num_steps != self.flow.num_steps:
                raise ValueError(
                    f"engine.num_steps={engine.num_steps} != trainer "
                    f"num_steps={self.flow.num_steps}")
            if engine.scheduler != self.scheduler:
                raise ValueError(
                    f"engine scheduler {engine.scheduler!r} != trainer "
                    f"scheduler {self.scheduler!r} — rollout dynamics and "
                    "the update's logprob must match")
            if engine.mesh != self.mesh:
                raise ValueError(
                    f"engine mesh {engine.mesh} != trainer mesh "
                    f"{self.mesh} — build via ServingEngine.for_trainer")
        self._engine = engine

    def sde_mask(self, it: int) -> Optional[jnp.ndarray]:
        return None  # default: all steps stochastic (or all ODE)

    def _sample(self, params, cond: jax.Array, key: jax.Array,
                sde_mask) -> Trajectory:
        return rollout(self.adapter, params, cond, key, self.scheduler,
                       self.flow.num_steps, sde_mask,
                       sde_mode=self.sde_mode, remat=self.perf.remat,
                       remat_policy=self._remat_policy)

    def sample(self, params, cond: jax.Array, key: jax.Array, it: int = 0
               ) -> Trajectory:
        """cond: (P, Lc, D) prompt embeddings -> grouped trajectories."""
        cond_g = group_repeat(cond, self.flow.group_size)
        # the downstream *update* still shards/chunks the trajectory, so the
        # divisibility contract holds on both sampling paths
        distributed.check_batch_divisible(cond_g.shape[0], self.mesh,
                                          self.dist.microbatch)
        mask = self.sde_mask(it)
        if mask is None:     # concrete mask: jit shardings need a real leaf
            mask = jnp.ones((self.flow.num_steps,), bool)
        if self._engine is not None:
            return self._engine.rollout(params, cond_g, key, sde_mask=mask)
        return self._sample_jit(params, cond_g, key, mask)

    # -------------------------------------------------------------- rewards
    @property
    def offloads_rewards(self) -> bool:
        """Whether the frozen reward-tower params live in host memory
        (``perf.offload_rewards``) and are threaded into the rewards/fused
        jit as arguments."""
        return self._reward_store_host is not None

    def prefetch_reward_params(self) -> None:
        """Start the async H2D copy of the host-offloaded reward towers
        (no-op when ``perf.offload_rewards`` is off or a prefetch is
        already pending).  The TrainLoop calls this right after each
        dispatch so the transfer overlaps the in-flight step's device
        work; the next ``step`` consumes it via ``_take_reward_params``."""
        if self._reward_store_host is None or \
                self._reward_prefetch is not None:
            return
        self._reward_prefetch = perf_lib.prefetch_tree(
            self._reward_store_host, self._reward_put_sharding)

    def _take_reward_params(self):
        """The device copy of the reward towers for this step: the pending
        prefetch if the loop armed one, else a fresh (synchronously
        enqueued, still async) transfer."""
        rp, self._reward_prefetch = self._reward_prefetch, None
        if rp is None:
            rp = perf_lib.prefetch_tree(self._reward_store_host,
                                        self._reward_put_sharding)
        return rp

    def _rewards(self, x0: jax.Array, cond_meta: Dict, reward_params=None,
                 *, group_size: int
                 ) -> Tuple[Dict[str, jax.Array], jax.Array,
                            Dict[str, jax.Array]]:
        """Returns (raw rewards, advantages, reward stats) — the stats (the
        weight_map-weighted ``reward_mean`` the optimizer ascends plus the
        per-reward means) are computed ON DEVICE here, inside the
        rewards/fused jit, so ``step`` never dispatches per-metric eager
        reductions.  ``reward_params`` (``perf.offload_rewards``) is the
        host-offloaded tower store threaded in as a jit argument; None
        keeps the historical resident-constant path."""
        rew = self.loader.compute_all(x0, cond_meta, group_size=group_size,
                                      params=reward_params)
        adv = compute_advantages(self.flow.advantage_agg, rew,
                                 self.loader.weight_map(), group_size)
        weights = self.loader.weight_map()
        stats = {f"reward/{name}": r.astype(F32).mean()
                 for name, r in rew.items()}
        stats["reward_mean"] = sum(weights[name] * stats[f"reward/{name}"]
                                   for name in rew)
        return rew, adv, stats

    # --------------------------------------------------------------- update
    def loss_fn(self, params, traj: Trajectory, adv: jax.Array,
                key: jax.Array, *extras
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError

    def update_extras(self) -> Tuple:
        """Auxiliary pytrees threaded into the jitted update as *arguments*
        (never closure-captured: jit would bake them in as constants at
        trace time, silently freezing later updates — the NFT reference-
        policy bug).  Called by ``step`` before the state is replaced, so
        entries derived from ``self.state`` see the behavior policy."""
        return ()

    def update_extras_sharding(self):
        """Mesh layout of the ``update_extras()`` tuple for the jitted
        update — None replicates.  Trainers whose extras alias param-shaped
        trees (NFT's ref_params) override this so the update jit accepts
        them in their placed (model-sharded) layout under ``mp>1``."""
        return None

    def _update(self, state: RLState, traj: Trajectory, adv: jax.Array,
                key: jax.Array, extras: Tuple = ()
                ) -> Tuple[RLState, Dict[str, jax.Array]]:
        k = self.dist.microbatch
        if k and k > 1:
            (loss, aux), grads = distributed.accumulated_value_and_grad(
                self.loss_fn, state.params, traj, adv, key, extras, k)
        else:
            (loss, aux), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True)(state.params, traj, adv, key,
                                            *extras)
        with jax.named_scope("optim_adamw"):
            grads, gnorm = optim.clip_by_global_norm(grads,
                                                     self.opt_cfg.grad_clip)
        lr = self._lr(state.opt.step)
        new_p, new_opt = self.optimizer.update(state.params, grads, state.opt,
                                               self.opt_cfg, lr)
        aux = dict(aux)
        aux.update(loss=loss, grad_norm=gnorm, lr=lr)
        return RLState(new_p, new_opt), aux

    # ------------------------------------------------------------ iteration
    def step(self, cond: jax.Array, key: jax.Array, it: int = 0
             ) -> Dict[str, jax.Array]:
        """One full RL iteration: rollout -> rewards -> advantages -> update.

        cond: (P, Lc, cond_dim) prompt embeddings (from the preprocessing
        cache or a live encoder — the trainer doesn't know which: §2.2).

        Returns a flat dict of DEVICE scalars (including the weighted
        ``reward_mean`` matching the advantage aggregation — EarlyStop and
        the JSON log track the same objective the optimizer ascends);
        callers fetch them with one ``jax.device_get``, not one transfer
        per metric.  With ``perf.fuse_step`` the whole iteration is a
        single donated jit (``repro.perf.fused``)."""
        if self._fused_jit is not None and self._engine is None:
            cond_g = group_repeat(cond, self.flow.group_size)
            distributed.check_batch_divisible(cond_g.shape[0], self.mesh,
                                              self.dist.microbatch)
            mask = self.sde_mask(it)
            if mask is None:
                mask = jnp.ones((self.flow.num_steps,), bool)
            extras = self.update_extras()
            if self.offloads_rewards:
                self.state, metrics = self._fused_jit(
                    self.state, cond_g, key, jnp.int32(it), mask, extras,
                    self._take_reward_params())
            else:
                self.state, metrics = self._fused_jit(
                    self.state, cond_g, key, jnp.int32(it), mask, extras)
            return metrics
        k_s, k_u = jax.random.split(jax.random.fold_in(key, it))
        # host spans (profiler only): the enqueue of each program
        with jax.profiler.TraceAnnotation("repro.trainer.sample"):
            traj = self.sample(self.state.params, cond, k_s, it)
        cond_meta = {"cond": traj.cond}
        with jax.profiler.TraceAnnotation("repro.trainer.rewards"):
            if self.offloads_rewards:
                _, adv, reward_stats = self._rewards_jit(
                    traj.x0, cond_meta, self._take_reward_params())
            else:
                _, adv, reward_stats = self._rewards_jit(traj.x0, cond_meta)
        extras = self.update_extras()
        with jax.profiler.TraceAnnotation("repro.trainer.update"):
            self.state, metrics = self._update_jit(self.state, traj, adv,
                                                   k_u, extras)
        metrics.update(reward_stats)
        return metrics

    # ------------------------------------------------------------- helpers
    def velocity(self, params, x, t, cond):
        # loss-side velocity: block remat threads the backbone's per-layer
        # checkpointing through the forward the backward will rematerialize
        return self.adapter.velocity(
            params, x, t, cond, remat=perf_lib.block_remat(self.perf.remat))

    def memory_stats(self, cond: jax.Array) -> Dict[str, Dict]:
        """``compiled.memory_analysis()`` byte counts of the jitted update
        (and the fused step, when enabled) for a (P, Lc, cond_dim) prompt
        batch, plus a ``"state"`` entry with the RLState's canonical total
        vs per-device bytes under the active PartitionPlan — the FSDP
        memory win, visible in ``perf.log_memory``.  See
        ``repro.perf.memory``.  AOT introspection only: nothing runs, no
        live buffer is donated."""
        return perf_lib.update_memory(self, cond)

    def sample_timesteps(self, key: jax.Array, batch: int) -> jax.Array:
        """Timestep sampling strategies for the solver-agnostic algorithms
        (paper §3.2): uniform | logit_normal | discrete."""
        how = self.flow.timestep_sampling
        if how == "uniform":
            return jax.random.uniform(key, (batch,), F32, 0.02, 0.98)
        if how == "logit_normal":
            return jax.nn.sigmoid(jax.random.normal(key, (batch,), F32))
        if how == "discrete":
            grid = self.scheduler.timesteps(self.flow.num_steps)[:-1]
            idx = jax.random.randint(key, (batch,), 0, grid.shape[0])
            return grid[idx]
        raise ValueError(f"unknown timestep_sampling {how!r}")
