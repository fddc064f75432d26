"""Shared neural-net layers (pure functions over param pytrees).

Numerics policy: activations in ``cfg`` dtype (bf16 by default); norms,
softmax, and matmul accumulation in f32 (``preferred_element_type``).
REPRO_BF16_REDUCE=1 (perf knob, §Perf iteration): output projections whose
contraction dim is model-sharded (wo, w_down) accumulate in bf16 instead of
f32, halving the bytes of the partial-sum all-reduce the SPMD partitioner
inserts.  Per-device MXU accumulation quality is unchanged on TPU (the MXU
accumulates f32 internally per dot); only the cross-shard summation is bf16 —
the standard Megatron-style trade.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.params import P

F32 = jnp.float32


def reduce_dtype():
    """Accumulation dtype for model-sharded (partial-summed) contractions."""
    return jnp.bfloat16 if os.environ.get("REPRO_BF16_REDUCE") else F32


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_spec(d: int) -> P:
    return P((d,), ("norm",), "ones")


def rmsnorm(w: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with D even; positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                    # (D/2,)
    angles = positions.astype(F32)[..., None] * freqs     # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                   # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_spec(d: int, f: int) -> dict:
    return {
        "w_gate": P((d, f), ("embed", "mlp")),
        "w_up": P((d, f), ("embed", "mlp")),
        "w_down": P((f, d), ("mlp", "embed_r")),
    }


def mlp(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"],
                   preferred_element_type=F32)
    u = jnp.einsum("...d,df->...f", x, p["w_up"],
                   preferred_element_type=F32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, p["w_down"],
                      preferred_element_type=reduce_dtype()).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention math (jnp path).  ``attention.apply_full`` runs bidirectional
# attention at head sizes of 128 in the Pallas flash kernel instead where
# Pallas is on (``attention.flash_kernel_axes``); everything else runs here
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * jnp.finfo(jnp.float32).max


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: int) -> jax.Array:
    """(Sq, Sk) additive bias. window>0 => sliding-window of that width."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= dk <= dq
    if window > 0:
        ok &= dk > dq - window
    return jnp.where(ok, 0.0, NEG_INF)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      q_positions: Optional[jax.Array] = None,
                      k_positions: Optional[jax.Array] = None,
                      chunk_q: int = 1024,
                      scale: Optional[float] = None) -> jax.Array:
    """Memory-bounded attention: lax.scan over query chunks.

    q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H % K == 0 (GQA).
    Peak scores memory = B * H * chunk_q * Sk * 4 bytes instead of Sq * Sk.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)
    if k_positions is None:
        k_positions = jnp.arange(Sk, dtype=jnp.int32)

    qg = q.reshape(B, Sq, K, G, D)

    def one_chunk(q_chunk: jax.Array, qpos_chunk: jax.Array) -> jax.Array:
        # q_chunk: (B, C, K, G, D)
        s = jnp.einsum("bckgd,btkd->bckgt", q_chunk, k,
                       preferred_element_type=F32) * scale
        s = s + _mask_bias(qpos_chunk, k_positions, causal, window)[
            None, :, None, None, :]
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bckgt,btkd->bckgd", p, v,
                          preferred_element_type=F32).astype(q.dtype)

    if Sq <= chunk_q:
        out = one_chunk(qg, q_positions)
    else:
        n = Sq // chunk_q
        rem = Sq - n * chunk_q
        qs = qg[:, :n * chunk_q].reshape(B, n, chunk_q, K, G, D)
        ps = q_positions[:n * chunk_q].reshape(n, chunk_q)
        # scan over chunks (compile-time O(1) in Sq)
        outs = jax.lax.map(lambda args: one_chunk(*args),
                           (qs.transpose(1, 0, 2, 3, 4, 5), ps))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, n * chunk_q, K, G, Dv)
        if rem:
            tail = one_chunk(qg[:, n * chunk_q:], q_positions[n * chunk_q:])
            out = jnp.concatenate([out, tail], axis=1)
    return out.reshape(B, Sq, H, Dv)


def attention_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     k_new: jax.Array, v_new: jax.Array, *,
                     window: int = 0, cache_len: Optional[int] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """One-token decode attention.

    q: (B, 1, H, D); caches: (B, T, K, D); k_new/v_new: (B, 1, K, D).
    The new token attends to the full cache plus itself.  ``window`` is
    enforced structurally by the cache being window-sized, so no masking is
    needed here beyond validity of entries.
    """
    B, _, H, D = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    k = jnp.concatenate([k_cache, k_new], axis=1)        # (B, T+1, K, D)
    v = jnp.concatenate([v_cache, v_new], axis=1)
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k, preferred_element_type=F32) * scale
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v, preferred_element_type=F32)
    return o.reshape(B, 1, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked cross-entropy (vocab can be 150k; never materialise full logits)
# ---------------------------------------------------------------------------

def chunked_ce_loss(hidden: jax.Array, w_vocab: jax.Array,
                    labels: jax.Array, *, chunk: int = 512) -> jax.Array:
    """hidden: (B, S, d); w_vocab: (d, V); labels: (B, S) int32.

    Scans over sequence chunks so the (tokens, V) logit block peaks at
    B*chunk*V instead of B*S*V.  Each chunk is rematerialised in backward.
    """
    B, S, d = hidden.shape
    V = w_vocab.shape[1]
    chunk = min(chunk, S)
    n = S // chunk

    @jax.checkpoint
    def chunk_loss(h_c: jax.Array, y_c: jax.Array) -> jax.Array:
        logits = jnp.einsum("btd,dv->btv", h_c, w_vocab,
                            preferred_element_type=F32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    hs = hidden[:, :n * chunk].reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    ys = labels[:, :n * chunk].reshape(B, n, chunk).transpose(1, 0, 2)

    def body(tot, xs):
        h_c, y_c = xs
        return tot + chunk_loss(h_c, y_c), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32), (hs, ys))
    rem = S - n * chunk
    if rem:
        total = total + chunk_loss(hidden[:, n * chunk:], labels[:, n * chunk:])
    return total / (B * S)


# ---------------------------------------------------------------------------
# Time embedding (flow / DiT conditioning)
# ---------------------------------------------------------------------------

def timestep_embedding(t: jax.Array, dim: int, max_period: float = 1e4
                       ) -> jax.Array:
    """t: (B,) in [0,1] -> (B, dim) sinusoidal features."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period) * jnp.arange(half, dtype=F32) / half)
    args = t.astype(F32)[:, None] * freqs[None, :] * 1000.0
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb
