"""Device-mesh construction for distributed RL training.

Two axes: ``"data"`` — sharded over prompts×groups batches — and
``"model"`` — params and AdamW moments sharded over it per the
:class:`repro.distributed.PartitionPlan`.  The mesh is only built when more
than one device participates: ``train_mesh`` returns ``None`` for
``dp×mp=1`` so every caller degrades to the exact single-device code path
(plain ``jax.jit``, no resharding, no collectives).  With ``mp=1`` the mesh
is the historical 1-D ``("data",)`` layout — bit-identical to the
replicated path this module shipped before the second axis existed.

Axis resolution (``resolve_axes``): a configured size of 0 means "auto" —
``data_parallel=0`` claims every local device *not* claimed by
``model_parallel``; ``model_parallel=0`` claims every device not claimed
by ``data_parallel`` (both 0 resolves to all-data, the historical
``data_parallel=0`` meaning).  ``dp×mp`` is validated against
``jax.local_device_count()`` with an actionable XLA_FLAGS hint.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from repro.config import DistConfig

DATA_AXIS = "data"
MODEL_AXIS = "model"


def _resolve_axis(name: str, requested: int, available: int,
                  total: Optional[int] = None) -> int:
    """Resolve one mesh-axis size: 0 -> all ``available`` devices, otherwise
    the configured count validated against what is actually there.  ``total``
    is the whole-mesh device count to suggest in the over-subscription hint
    (defaults to the requested axis size)."""
    if requested < 0:
        raise ValueError(f"dist.{name} must be >= 0, got {requested}")
    if requested == 0:
        return max(available, 1)
    if requested > available:
        want = total or requested
        raise ValueError(
            f"dist.{name}={requested} but only {available} device(s) are "
            f"available for this axis — launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={want} (CPU) or on a "
            f"{want}-device accelerator host")
    return requested


def resolve_axes(dist: DistConfig) -> tuple:
    """``(data_parallel, model_parallel)`` resolved against the local device
    count.  0 on either axis means "all devices not claimed by the other":
    ``model_parallel`` is resolved first when explicitly configured, so
    ``data_parallel=0`` fills the remainder; with ``model_parallel=0`` the
    data axis resolves first and the model axis takes what is left."""
    n_local = jax.local_device_count()
    dp_req = dist.data_parallel
    mp_req = getattr(dist, "model_parallel", 1)
    if mp_req == 0:
        dp = _resolve_axis("data_parallel", dp_req, n_local)
        mp = n_local // dp
    else:
        mp = _resolve_axis("model_parallel", mp_req, n_local)
        dp = _resolve_axis("data_parallel", dp_req, n_local // mp,
                           total=dp_req * mp if dp_req > 0 else None)
    return dp, mp


def resolve_data_parallel(dist: DistConfig) -> int:
    """Resolved "data" axis size (see :func:`resolve_axes`)."""
    return resolve_axes(dist)[0]


def resolve_model_parallel(dist: DistConfig) -> int:
    """Resolved "model" axis size (see :func:`resolve_axes`)."""
    return resolve_axes(dist)[1]


def train_mesh(dist: DistConfig) -> Optional[Mesh]:
    """The training mesh over the first ``dp×mp`` *local* devices (counts
    were validated against local_device_count — in a multi-process run
    jax.devices() would include other hosts' non-addressable devices):

    * ``dp×mp == 1`` -> ``None`` (exact single-device fast path);
    * ``mp == 1``    -> 1-D ``Mesh((dp,), ("data",))`` — literally the
      historical data-parallel mesh, so jit layouts are bit-identical to
      the pre-"model"-axis path;
    * otherwise      -> 2-D ``Mesh((dp, mp), ("data", "model"))``.
    """
    dp, mp = resolve_axes(dist)
    if dp * mp <= 1:
        return None
    devices = jax.local_devices()[:dp * mp]
    if mp == 1:
        return Mesh(devices, (DATA_AXIS,))
    return Mesh(np.asarray(devices).reshape(dp, mp),
                (DATA_AXIS, MODEL_AXIS))


def data_mesh(dist: DistConfig) -> Optional[Mesh]:
    """Compatibility alias for :func:`train_mesh` (the historical 1-D entry
    point; the returned mesh is 2-D whenever ``model_parallel > 1``)."""
    return train_mesh(dist)


def mesh_dp(mesh: Optional[Mesh]) -> int:
    """Size of the "data" axis (1 for no mesh)."""
    return 1 if mesh is None else int(mesh.shape.get(DATA_AXIS, 1))


def mesh_mp(mesh: Optional[Mesh]) -> int:
    """Size of the "model" axis (1 for no mesh or a 1-D data mesh)."""
    return 1 if mesh is None else int(mesh.shape.get(MODEL_AXIS, 1))
