"""Logical-axis sharding rules.

Every parameter and activation in the framework carries a tuple of *logical*
axis names.  A rule table maps logical names -> mesh axes, which yields a
``PartitionSpec``.  This keeps the model code mesh-agnostic: the same model
runs on 1 CPU device, a 256-chip pod, or the 512-chip two-pod mesh purely by
swapping the rule table.

Mesh axes: ``("pod", "data", "model")`` multi-pod or ``("data", "model")``.

Baseline layout (documented in DESIGN.md §5):
  * batch            -> ("pod", "data")
  * attention heads, FFN hidden, expert hidden, vocab -> "model"
  * parameters additionally FSDP-sharded over "data" on their embed axis for
    training shapes (zero-3 style)
  * long-context decode: KV cache sequence -> "data" (distributed flash-decode)
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Axes = Tuple[Optional[str], ...]
RuleTable = Dict[str, Union[str, Tuple[str, ...], None]]

DATA_AXIS = "data"
MODEL_AXIS = "model"


def split_axis(mesh, name: str) -> Optional[str]:
    """``name`` where the abstract ``mesh`` (``jax.sharding.
    get_abstract_mesh()``) has that axis over more than one device and a
    ``shard_map`` may still split over it (it is not already manual);
    otherwise None."""
    if (mesh.empty or name not in mesh.axis_names
            or name in mesh.manual_axes or mesh.shape[name] == 1):
        return None
    return name


def _moe_mode() -> str:
    import os
    return os.environ.get("REPRO_MOE_MODE", "tensor")


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the batch dim is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def param_rules(mesh: Mesh, *, fsdp: bool = True, train: bool = True) -> RuleTable:
    """Rules for parameter logical axes."""
    fsdp_axis = "data" if (fsdp and "data" in mesh.axis_names) else None
    return {
        "embed": fsdp_axis,        # d_model rows of big matrices (zero-3)
        "embed_r": fsdp_axis,      # d_model as the output dim (w_down, wo):
                                   # same zero-3 treatment for params; the
                                   # activation rule maps it to None
        "heads": "model",
        "kv_heads": "model",
        "q_lora": None,
        "kv_lora": None,
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": None,           # tensor/dense: experts replicated over mesh
        "experts_mdl": "model",    # ep_model: experts sharded over model axis
        "moe_f": None,             # per-expert hidden dim in ep_model mode
        # expert-weight d_model dims: always fsdp-sharded in storage; see
        # gathered_param_rules for the at-use layout per mode
        "moe_in": fsdp_axis,
        "moe_out": fsdp_axis,
        "layers": None,
        "groups": None,
        "state": None,
        "conv": None,
        "inner": "model",          # ssm d_inner
        "ssm_heads": "model",
        "norm": None,
        "latent": None,
        "time": None,
    }


def act_rules(mesh: Mesh, *, seq_shard: bool = False) -> RuleTable:
    """Rules for activation / cache logical axes."""
    b = batch_axes(mesh)
    return {
        "batch": b if b else None,
        "seq": None,
        "cache_seq": ("data" if (seq_shard and "data" in mesh.axis_names) else None),
        "embed": None,
        "embed_r": None,
        "heads": "model",
        "kv_heads": "model",
        "kv_lora": None,
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": None,
        "experts_mdl": "model",    # dispatch-buffer all-to-all target
        "moe_f": None,
        "moe_in": None,
        "moe_out": None,
        "state": None,
        "inner": "model",
        "ssm_heads": "model",
        "latent": None,
        "cond": None,
        "group": None,
        "time": None,
        "scalar": None,
    }


def pspec(axes: Axes, rules: RuleTable) -> PartitionSpec:
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        else:
            m = rules.get(a, None)
            out.append(m)
    # PartitionSpec trailing Nones are fine; keep explicit length
    return PartitionSpec(*out)


def named(mesh: Mesh, axes: Axes, rules: RuleTable) -> NamedSharding:
    return NamedSharding(mesh, pspec(axes, rules))


def tree_pspecs(axes_tree, rules: RuleTable):
    """Map a pytree of logical-axes tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda ax: pspec(ax, rules),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(mesh: Mesh, axes_tree, rules: RuleTable):
    return jax.tree.map(
        lambda ax: NamedSharding(mesh, pspec(ax, rules)),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


# ---------------------------------------------------------------------------
# Weight-gathered FSDP (zero-3 done right)
#
# With parameters fsdp-sharded on their embed axis and layers driven by
# lax.scan, XLA's SPMD partitioner may choose to keep the *weight* shards in
# place and instead all-gather the ACTIVATIONS to global batch + all-reduce
# the d-partial matmul outputs — catastrophically more traffic (observed:
# ~1.3 TB/step/device on smollm train_4k).  The fix is the MaxText approach:
# constrain the per-layer weight slices to the GATHERED layout inside the
# scan body, so each layer all-gathers its (small) weights over the data
# axis and activations stay batch-sharded.
#
# The constraint is installed per-trace via set_param_gather(); model code
# calls constrain_params(blk_params, axes_tree) at the top of each block.
# ---------------------------------------------------------------------------

_GATHER_CTX: dict = {"mesh": None, "param_rules": None, "act_rules": None}


def gathered_param_rules(mesh: Mesh) -> RuleTable:
    """Layout of a weight slice while it is being USED: model-sharded axes
    stay sharded; the fsdp (data) shard is gathered — EXCEPT expert weights,
    which stay fsdp-sharded (gathering all E experts per layer would move
    E/top_k more bytes than the activation traffic it saves)."""
    r = param_rules(mesh, fsdp=False)
    if _moe_mode() != "ep_model":
        # tensor/dense: keep expert weights fsdp-sharded (skip the gather)
        stored = param_rules(mesh, fsdp=True)
        r["moe_in"] = stored["moe_in"]
        r["moe_out"] = stored["moe_out"]
    # ep_model: experts live on the model axis with full f, so gathering the
    # (1/16-sized) d shards at use is cheap and keeps the matmul local
    return r


def set_param_gather(mesh: Optional[Mesh],
                     prules: Optional[RuleTable] = None,
                     arules: Optional[RuleTable] = None) -> None:
    """Install (or clear, with mesh=None) the per-trace constraint context."""
    _GATHER_CTX["mesh"] = mesh
    _GATHER_CTX["param_rules"] = (
        prules if prules is not None else
        (gathered_param_rules(mesh) if mesh is not None else None))
    _GATHER_CTX["act_rules"] = (
        arules if arules is not None else
        (act_rules(mesh) if mesh is not None else None))


def _constrain(x, axes: Axes, rules: RuleTable, mesh: Mesh):
    ax = tuple(axes)
    if len(ax) >= x.ndim:      # scan slices drop leading stacking axes
        ax = ax[len(ax) - x.ndim:]
    else:
        ax = (None,) * (x.ndim - len(ax)) + ax
    spec = pspec(ax, rules)
    # never force a non-divisible dim onto a mesh axis (XLA would pad and
    # recombine with full-tensor collectives — e.g. kv_heads=5 on model=16)
    cleaned = []
    for dim, entry in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if entry is None:
            cleaned.append(None)
            continue
        names_ = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for n in names_:
            size *= mesh.shape[n]
        cleaned.append(entry if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*cleaned)))


def constrain_params(params, axes_tree):
    """Apply the gathered-weight constraint if one is installed.

    ``params`` drives the map (array leaves); ``axes_tree`` holds a logical-
    axes tuple at each corresponding leaf position (tuples are treated as
    leaves by flatten-up-to)."""
    mesh, rules = _GATHER_CTX["mesh"], _GATHER_CTX["param_rules"]
    if mesh is None:
        return params
    return jax.tree.map(lambda p, ax: _constrain(p, ax, rules, mesh),
                        params, axes_tree)


def constrain_act(x, axes: Axes):
    """Pin an activation to the canonical layout (batch-sharded)."""
    mesh, rules = _GATHER_CTX["mesh"], _GATHER_CTX["act_rules"]
    if mesh is None:
        return x
    return _constrain(x, axes, rules, mesh)
