"""GQA attention block: param spec + full-sequence / decode application.

Supports grouped-query attention, optional per-head q/k RMSNorm (Qwen3),
sliding windows (enables long_500k for dense archs) and KV caches.

Sharding: ``wq``/``wo`` carry the ``"heads"`` logical axis and ``wk``/``wv``
carry ``"kv_heads"``, so under ``dist.model_parallel>1`` the
:class:`~repro.distributed.PartitionPlan` shards the projections
head-parallel when the head count divides the model axis (``MODEL_SHARDABLE``
priority; GQA kv heads may stay replicated when K < mp).  Declared here via
:class:`repro.models.params.P` — the distributed layer never names modules.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding as shlib
from repro.config import ArchConfig
from repro.kernels import ops
from repro.models import layers
from repro.models.params import P, model_shard_dim


class KVCache(NamedTuple):
    k: jax.Array     # (B, T, K, D)
    v: jax.Array     # (B, T, K, D)


def spec(cfg: ArchConfig) -> Dict:
    d, H, K = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    s = {
        "wq": P((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": P((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((H, hd, d), ("heads", "head_dim", "embed_r")),
    }
    if cfg.qk_norm:
        s["q_norm"] = P((hd,), ("head_dim",), "ones")
        s["k_norm"] = P((hd,), ("head_dim",), "ones")
    return s


def _qkv(p: Dict, cfg: ArchConfig, x: jax.Array,
         positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"],
                   preferred_element_type=layers.F32).astype(x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"],
                   preferred_element_type=layers.F32).astype(x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"],
                   preferred_element_type=layers.F32).astype(x.dtype)
    if cfg.qk_norm:
        q = layers.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = layers.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def flash_kernel_axes(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, window: int
                      ) -> Optional[Tuple[Optional[str], Optional[str]]]:
    """The (data, model) mesh axes over which ``apply_full`` runs the core
    of attention (scores, softmax, PV) in the Pallas flash kernel, each None
    where it is not split; None where ``layers.attention_chunked`` runs
    instead.  The kernel takes bidirectional attention without a window,
    with equal q and v head sizes that are whole lane tiles (128), with
    Pallas on.  Under a mesh it runs per shard: the batch on the data axis,
    and the heads and kv heads on the model axis where the PartitionPlan
    splits the projections (``params.model_shard_dim``).  A mesh it cannot
    split over so, or a ``shard_map`` that owns some axes while others are
    left to split, keeps the chunked path."""
    D = q.shape[-1]
    if not (ops.pallas_enabled() and not causal and window == 0
            and D == v.shape[-1] and D % 128 == 0):
        return None
    mesh = jax.sharding.get_abstract_mesh()
    data = shlib.split_axis(mesh, shlib.DATA_AXIS)
    model = shlib.split_axis(mesh, shlib.MODEL_AXIS)
    if (data or model) and mesh.manual_axes:
        return None
    if data and q.shape[0] % mesh.shape[data]:
        return None
    if model and any(model_shard_dim((n,), (name,), mesh.shape[model]) != 0
                     for n, name in ((q.shape[2], "heads"),
                                     (k.shape[2], "kv_heads"))):
        return None
    return data, model


def apply_full(p: Dict, cfg: ArchConfig, x: jax.Array, *,
               causal: bool = True, window: int = 0,
               positions: Optional[jax.Array] = None,
               return_cache: bool = False
               ) -> Tuple[jax.Array, Optional[KVCache]]:
    """Full-sequence attention (train / prefill). x: (B, S, d)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _qkv(p, cfg, x, positions)
    axes = flash_kernel_axes(q, k, v, causal=causal, window=window)
    if axes is None:
        o = layers.attention_chunked(q, k, v, causal=causal, window=window,
                                     q_positions=positions,
                                     k_positions=positions)
    else:
        o = ops.flash_attention(q, k, v, causal=False, data_axis=axes[0],
                                model_axis=axes[1])
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=layers.reduce_dtype()
                     ).astype(x.dtype)
    cache = KVCache(k, v) if return_cache else None
    return out, cache


def apply_decode(p: Dict, cfg: ArchConfig, x: jax.Array, cache: KVCache,
                 pos: jax.Array, *, window: int = 0
                 ) -> Tuple[jax.Array, KVCache]:
    """One-token decode. x: (B, 1, d); pos: scalar int32 position index.

    The cache holds the previous ``T`` KV entries (window-sized when sliding
    windows are active).  Returns output and the rolled cache.
    """
    B = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _qkv(p, cfg, x, positions)
    o = layers.attention_decode(q, cache.k, cache.v, k_new, v_new,
                                window=window)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=layers.reduce_dtype()
                     ).astype(x.dtype)
    # roll the cache: drop the oldest entry, append the new one (ring-buffer
    # semantics; keeps the cache shape static for jit)
    k_c = jnp.concatenate([cache.k[:, 1:], k_new], axis=1)
    v_c = jnp.concatenate([cache.v[:, 1:], v_new], axis=1)
    return out, KVCache(k_c, v_c)


def init_cache_shape(cfg: ArchConfig, batch: int, cache_len: int
                     ) -> Tuple[Tuple[int, ...], Tuple]:
    hd = cfg.resolved_head_dim
    shape = (batch, cache_len, cfg.n_kv_heads, hd)
    axes = ("batch", "cache_seq", "kv_heads", "head_dim")
    return shape, axes
