"""``repro.perf`` — train-step performance subsystem.

The ROADMAP north-star's "make a hot path measurably faster" axis applied
to *training*: the RL update's backward otherwise stores full backbone
activations for every denoising step, each ``BaseTrainer.step`` dispatches
three separate jits, and the rollout body pays for both the SDE and ODE
branches even for statically pure-ODE trainers.  Everything here is driven
by :class:`repro.config.PerfConfig` (``--set perf.*`` from every front
door) and is a *runtime* choice — checkpoints move freely across policies.

* ``policy``   — PerfConfig validation, remat helpers, activation dtype
* ``fused``    — the single-jit sample→rewards→advantages→update step
* ``memory``   — ``compiled.memory_analysis()`` introspection
* ``offload``  — host-memory offload: reward towers + remat residuals

Exactness contract (asserted in tests/test_perf.py / test_pipeline.py):

* ``remat="scan"``  : bit-identical to ``"none"`` on XLA:CPU — a
  ``jax.checkpoint`` around a ``lax.scan`` body is structurally isolated,
  so the recompute graph matches the original exactly.
* ``remat="block"`` : f32-rounding-equal (rtol 1e-5 / atol 1e-6) — XLA
  re-fuses open-graph remat and reassociates f32 reductions.
* ``fuse_step``     : f32-rounding-equal to the three-jit path (same ops,
  different compiled program).
* ``offload_rewards`` : f32-rounding-equal — reward params arrive as jit
  *arguments* instead of baked-in constants, a different compiled program.
* ``remat_offload``   : f32-rounding-equal — saved-to-host residuals
  replace recompute in the scan backward.
"""
from repro.perf.fused import make_fused_step
from repro.perf.memory import analysis_dict, lower_step, update_memory
from repro.perf.offload import (offload_param_store, prefetch_tree,
                                reward_tower_report, tree_bytes)
from repro.perf.policy import (REMAT_MODES, block_remat, remat_policy,
                               resolve_policy_dtype, validate)

__all__ = [
    "REMAT_MODES", "block_remat", "remat_policy", "resolve_policy_dtype",
    "validate", "make_fused_step", "analysis_dict", "lower_step",
    "update_memory",
    "offload_param_store", "prefetch_tree", "reward_tower_report",
    "tree_bytes",
]
