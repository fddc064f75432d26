"""Host memory offload for the train step (``perf.offload_rewards`` /
``perf.remat_offload``).

Two independent mechanisms, one idea — device HBM should hold what the
*current* computation needs, not everything that is frozen:

* **Reward towers** (``offload_rewards``): the frozen reward-model params
  are needed only during the (cheap) reward phase of each step, yet the
  historical path kept them device-resident for the whole run — worse,
  closure-captured inside the rewards jit as trace-time constants.
  :func:`offload_param_store` parks them in host memory; the trainer then
  threads them into the rewards/fused jit as *arguments* (never closures —
  the PR-2 constant-capture class, jaxlint R003) and the TrainLoop starts
  the H2D copy right after each dispatch (:func:`prefetch_tree`), so the
  transfer overlaps the in-flight step's rollout+backward.  Exactness:
  f32-rounding-equal to the resident path (same ops, but arguments compile
  a different program than baked-in constants).

* **Remat residuals** (``remat_offload``): ``remat="scan"`` recomputes the
  scan body in the backward; :func:`remat_offload_policy` builds the
  ``jax.checkpoint_policies.save_and_offload_only_these_names`` policy
  that instead *saves* the named velocity residual to host memory and
  reloads it in the backward — trading recompute for PCIe traffic.  The
  named residuals are tagged in ``repro.core.rollout`` / the GRPO loss
  scan via ``jax.ad_checkpoint.checkpoint_name``.

Backend notes: memory *kinds* are how XLA addresses host memory from
within a compiled program.  Every backend of the installed JAX (the CPU
one included) exposes ``pinned_host`` alongside its ``device`` default, so
the offloaded leaves stay jax arrays under a host-kind sharding.  A backend
without a distinct host kind gets plain ``device_get`` numpy arrays on
CPU; on an accelerator that is an error, never a silent move to numpy.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# preference order: pinned host memory DMAs back to device without a
# staging copy; unpinned is still off-HBM
_HOST_KINDS = ("pinned_host", "unpinned_host")

#: residual names the remat-offload policy saves to host (tagged with
#: ``checkpoint_name`` in the rollout / GRPO-loss scan bodies)
OFFLOAD_NAMES = ("velocity",)


def host_memory_kind(device=None) -> Optional[str]:
    """A host memory kind addressable by ``device`` and distinct from its
    default memory, or None when the default already lives on the host."""
    if device is None:
        device = jax.local_devices()[0]
    kinds = {m.kind for m in device.addressable_memories()}
    default = device.default_memory().kind
    for kind in _HOST_KINDS:
        if kind in kinds and kind != default:
            return kind
    return None


def tree_bytes(tree: Any) -> int:
    """Total byte footprint of a pytree's leaves (host-side arithmetic
    over shapes — nothing is fetched or compiled)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        size = 1
        for d in jnp.shape(leaf):
            size *= int(d)
        total += size * jnp.dtype(jnp.result_type(leaf)).itemsize
    return int(total)


def offload_tree(tree: Any) -> Any:
    """Move a pytree to host memory.  On backends with a distinct host
    memory kind the leaves stay jax arrays under a host-kind sharding
    (so :func:`prefetch_tree` is a pure memory-kind transfer); on a CPU
    backend without one the leaves become numpy arrays via one
    ``device_get``.  An accelerator without a host kind raises."""
    kind = host_memory_kind()
    if kind is None:
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"{jax.default_backend()} device exposes no host memory "
                "kind; perf.offload_rewards cannot park the reward towers")
        return jax.device_get(tree)
    sharding = jax.sharding.SingleDeviceSharding(jax.local_devices()[0],
                                                 memory_kind=kind)
    return jax.device_put(tree, sharding)


def prefetch_tree(host_tree: Any, sharding=None) -> Any:
    """Start the async H2D copy of a host-offloaded pytree and return the
    device arrays immediately (``jax.device_put`` enqueues; the transfer
    overlaps whatever device work is already in flight).  ``sharding``
    replicates the tree over a mesh when the trainer has one."""
    if sharding is None:
        return jax.device_put(host_tree)
    return jax.device_put(host_tree, sharding)


def offload_param_store(loader) -> Dict[str, Any]:
    """Park a :class:`~repro.core.rewards.MultiRewardLoader`'s param store
    in host memory and rebase the loader onto the host copies.  Returns
    the host store the trainer threads into the rewards jit.  Rebasing
    keeps any accidental closure capture *correct* (the values are the
    same) — it would merely forfeit the memory win, and jaxlint R003
    polices that capture anyway."""
    host = {mid: offload_tree(p) for mid, p in loader.param_store().items()}
    loader.rebase(host)
    return host


def reward_tower_report(trainer) -> Dict[str, Any]:
    """The ``perf.log_memory`` accounting entry for the reward towers:
    their total byte footprint, what stays device-resident under the
    active policy, and the device bytes ``offload_rewards`` freed."""
    total = tree_bytes(trainer.loader.param_store())
    off = trainer.offloads_rewards
    return {
        "tower_bytes": total,
        "device_resident_bytes": 0 if off else total,
        "device_bytes_freed": total if off else 0,
        "offloaded": off,
    }


def remat_offload_policy():
    """The ``jax.checkpoint`` policy for ``perf.remat_offload``: save the
    :data:`OFFLOAD_NAMES` residuals to host memory instead of recomputing
    them in the scan backward; everything unnamed is still rematerialized.
    Returns None when this jax predates named offload policies (the knob
    then degrades to plain ``remat="scan"``)."""
    try:
        make = jax.checkpoint_policies.save_and_offload_only_these_names
    except AttributeError:               # pragma: no cover - old jax
        return None
    try:
        return make(names_which_can_be_saved=[],
                    names_which_can_be_offloaded=list(OFFLOAD_NAMES),
                    offload_src="device", offload_dst="pinned_host")
    except TypeError:                    # pragma: no cover - API drift
        return None
