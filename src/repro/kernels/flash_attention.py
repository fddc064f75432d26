"""Flash attention Pallas kernel (TPU target), with a custom VJP.

Online-softmax attention with GQA, causal and sliding-window masks.  The
forward is one Pallas kernel that keeps each score block in VMEM; the
backward is plain jnp from the forward's residuals (q, k, v, o and the row
log-sum-exp).

Precision, as ``models.layers.attention_chunked``: MXU operands in the input
dtype with f32 accumulation, the softmax statistics in f32, ``p`` cast to the
input dtype before the PV product.

Layout.  q, k and v are read, and o written, with the heads folded into the
leading axis, ``(B*H, S, D)``, a ``(1, block, D)`` block per grid step.  On
TPU, XLA lays the projections' outputs out head-major (tiles of 8 tokens by
128 features of one head), so that view and the transposes to and from it
are bitcasts.  A ``(B, S, H*D)`` view, tiles of 8 tokens by 128 features
across the heads, is not: XLA then adds relayout copies of the operands
(AOT compile of the FLUX-width velocity for a v5e).

Blocks, from the shape.  A key length of at most ``WHOLE_KV_MAX`` keys is
one kv block (one head's K and V, plus a q block's f32 scores, in VMEM).
Longer key lengths take kv blocks of ``DEFAULT_BLOCK_K`` keys, and a padded
key tail is masked.  Either way the running max, sum and output live in VMEM
scratch across the sequential kv grid axis; with one kv block the online
softmax reduces to the plain one (the first block's rescale is by 0).  The
q block is the largest multiple of 8 dividing Sq whose f32 scores fit
``SCORE_BYTES``; where no divisor of at least 128 rows fits, Sq is split
into equal blocks of a multiple of 8 rows that fit, and q is padded to fill
them.  The grid is (B, H, q blocks, kv blocks); K and V stay resident while
the q blocks of one head go by.

Causal and window masks are applied, not skipped: kv blocks above the
diagonal are fetched and masked.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
SUBLANES = 8
# 4 MiB gives 520-row q blocks at 1040 keys.  On one TPU v5e the kernel at
# (16, 1040, 24, 128) bf16 took 2.75 ms a call there, 3.22 ms at 208 rows
# and 2.72 ms at 1040 rows.
SCORE_BYTES = 4 * 2**20        # f32 scores of one grid step
WHOLE_KV_MAX = 4096            # longest key length taken as one kv block
DEFAULT_BLOCK_K = 512
VMEM_LIMIT_BYTES = 64 * 2**20
NEG_INF = -1e30


class Blocks(NamedTuple):
    q: int
    k: int


def choose_blocks(sq: int, sk: int, block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> Blocks:
    """The (q, kv) block sizes for query length ``sq`` and key length
    ``sk``; an explicit size is taken as it is, cut to the length."""
    bk = min(block_k or (sk if sk <= WHOLE_KV_MAX else DEFAULT_BLOCK_K), sk)
    if block_q:
        return Blocks(min(block_q, sq), bk)
    rows = max(SUBLANES, SCORE_BYTES // (4 * bk) // SUBLANES * SUBLANES)
    bq = next((r for r in range(min(rows, sq) // SUBLANES * SUBLANES, 0,
                                -SUBLANES) if sq % r == 0), 0)
    if bq < min(LANES, sq):         # no divisor worth having: pad q
        bq = _round_up(_cdiv(sq, _cdiv(sq, rows)), SUBLANES)
    return Blocks(bq, bk)


def _cdiv(n: int, m: int) -> int:
    return -(-n // m)


def _round_up(n: int, m: int) -> int:
    return _cdiv(n, m) * m


def _attn_kernel(q_ref, k_ref, v_ref, *refs, scale: float, causal: bool,
                 window: int, kv_len: Optional[int], block_q: int,
                 block_k: int, n_kv_blocks: int, with_lse: bool):
    o_ref, lse_ref = refs[0], (refs[1] if with_lse else None)
    m_scr, l_scr, acc_scr = refs[-3:]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    if causal or window > 0 or kv_len is not None:
        shape = (block_q, block_k)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        ok = jnp.ones(shape, jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window > 0:
            ok &= k_pos > q_pos - window
        if kv_len is not None:
            ok &= k_pos < kv_len
        s = jnp.where(ok, s, NEG_INF)
    v = v_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    m_prev = m_scr[...]                                 # (Bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                              # (Bq, Bk)
    alpha = jnp.exp(m_prev - m_new)                     # (Bq, 1)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _final():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = jnp.broadcast_to(m_scr[...] + jnp.log(l),
                                             (block_q, LANES))


def _pad_rows(x: jax.Array, n: int) -> jax.Array:
    if x.shape[1] == n:
        return x
    return jnp.pad(x, ((0, 0), (0, n - x.shape[1])) + ((0, 0),) * (x.ndim - 2))


class _Config(NamedTuple):
    causal: bool
    window: int
    block_q: Optional[int]
    block_k: Optional[int]
    interpret: bool


def _forward(q, k, v, cfg: _Config, with_lse: bool):
    """(o, lse or None); lse is (B, H, Sq padded, 128) f32, each row's
    log-sum-exp of the scaled scores repeated over the lanes."""
    B, Sq, H, D = q.shape
    Sk, K, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // K
    bq, bk = choose_blocks(Sq, Sk, cfg.block_q, cfg.block_k)
    sq, sk = _round_up(Sq, bq), _round_up(Sk, bk)
    n_q, n_k = sq // bq, sk // bk
    q, k, v = _pad_rows(q, sq), _pad_rows(k, sk), _pad_rows(v, sk)

    # heads folded into the leading axis: a bitcast of the layout XLA gives
    # the projections' outputs on TPU (module docstring)
    args = (q.transpose(0, 2, 1, 3).reshape(B * H, sq, D),
            k.transpose(0, 2, 1, 3).reshape(B * K, sk, D),
            v.transpose(0, 2, 1, 3).reshape(B * K, sk, Dv))
    q_map = lambda b, h, i, j: (b * H + h, i, 0)
    kv_map = lambda b, h, i, j: (b * K + h // G, j, 0)

    out_shape = [jax.ShapeDtypeStruct((B * H, sq, Dv), q.dtype)]
    out_specs = [pl.BlockSpec((1, bq, Dv), q_map)]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, sq, LANES), F32))
        out_specs.append(pl.BlockSpec((1, 1, bq, LANES),
                                      lambda b, h, i, j: (b, h, i, 0)))
    scratch = [pltpu.VMEM((bq, 1), F32), pltpu.VMEM((bq, 1), F32),
               pltpu.VMEM((bq, Dv), F32)]
    kernel = functools.partial(
        _attn_kernel, scale=D ** -0.5, causal=cfg.causal, window=cfg.window,
        kv_len=Sk if sk != Sk else None, block_q=bq, block_k=bk,
        n_kv_blocks=n_k, with_lse=with_lse)
    itemsize = q.dtype.itemsize
    outs = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bk, Dv), kv_map)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * sq * sk * (D + Dv),
            transcendentals=B * H * sq * sk,
            bytes_accessed=itemsize * (B * H * sq * (D + Dv)
                                       + n_q * B * K * sk * (D + Dv))),
        interpret=cfg.interpret,
        name="flash_attention",
    )(*args)
    o = outs[0].reshape(B, H, sq, Dv).transpose(0, 2, 1, 3)
    return o[:, :Sq], (outs[1] if with_lse else None)


BWD_CHUNK_Q = 1024     # query rows whose scores the backward holds at once


def _backward(q, k, v, o, lse, do, *, causal: bool, window: int):
    """dq, dk, dv from the residuals, at the precisions of XLA's autodiff
    of ``attention_chunked``: the cotangent in f32, the recomputed ``p`` in
    the input dtype for dv, dp rounded to the input dtype, the score
    gradient in f32; each result in its input's dtype.  ``p`` comes from
    the saved log-sum-exp, and each row's ``sum(dp * p)`` from ``do . o``
    (equal up to rounding), where autodiff recomputes the softmax and sums
    over the keys.  Query rows go in chunks of at most ``BWD_CHUNK_Q``, as
    ``attention_chunked``'s do."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = D ** -0.5
    n = _cdiv(Sq, BWD_CHUNK_Q)
    c = _round_up(_cdiv(Sq, n), SUBLANES) if n > 1 else Sq
    rows = n * c
    lse = lse[:, :, :Sq, 0].transpose(0, 2, 1).reshape(B, Sq, K, G)
    pad = functools.partial(_pad_rows, n=rows)
    qg = pad(q).reshape(B, n, c, K, G, D)
    dog = pad(do).reshape(B, n, c, K, G, -1)
    og = pad(o).reshape(B, n, c, K, G, -1)
    lseg = pad(lse).reshape(B, n, c, K, G)
    k_pos = jnp.arange(Sk, dtype=jnp.int32)

    def chunk(args):
        qc, doc, oc, lsec, i = args
        s = jnp.einsum("bckgd,btkd->bckgt", qc, k,
                       preferred_element_type=F32) * scale
        if causal or window > 0:
            q_pos = i * c + jnp.arange(c, dtype=jnp.int32)
            ok = jnp.ones((c, Sk), bool)
            if causal:
                ok &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                ok &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
        p32 = jnp.exp(s - lsec[..., None])
        do32 = doc.astype(F32)
        dv = jnp.einsum("bckgt,bckgd->btkd", p32.astype(v.dtype), do32,
                        preferred_element_type=F32)
        dp = jnp.einsum("bckgd,btkd->bckgt", do32, v,
                        preferred_element_type=F32).astype(v.dtype)
        delta = jnp.sum(do32 * oc.astype(F32), axis=-1, keepdims=True)
        ds = p32 * (dp.astype(F32) - delta) * scale
        dq = jnp.einsum("bckgt,btkd->bckgd", ds, k,
                        preferred_element_type=F32).astype(q.dtype)
        dk = jnp.einsum("bckgt,bckgd->btkd", ds, qc,
                        preferred_element_type=F32)
        return dq, dk, dv

    chunks = (qg.swapaxes(0, 1), dog.swapaxes(0, 1), og.swapaxes(0, 1),
              lseg.swapaxes(0, 1), jnp.arange(n, dtype=jnp.int32))
    if n == 1:
        dq, dk, dv = chunk(jax.tree.map(lambda x: x[0], chunks))
    else:
        dq, dk, dv = jax.lax.map(chunk, chunks)
        dq = dq.swapaxes(0, 1).reshape(B, rows, K, G, D)
        dk, dv = dk.sum(0), dv.sum(0)
    return (dq.reshape(B, rows, H, D)[:, :Sq], dk.astype(k.dtype),
            dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, cfg: _Config):
    return _forward(q, k, v, cfg, with_lse=False)[0]


def _attention_fwd(q, k, v, cfg: _Config):
    o, lse = _forward(q, k, v, cfg, with_lse=True)
    return o, (q, k, v, o, lse)


def _attention_bwd(cfg: _Config, res, do):
    return _backward(*res, do, causal=cfg.causal, window=cfg.window)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H % K == 0.

    Returns (B, Sq, H, Dv).  Positions are 0-based on both sides
    (self-attention layout); any Sq and Sk (padded inside).  Block sizes
    default to ``choose_blocks``'s.  Differentiable (custom VJP)."""
    return _attention(q, k, v, _Config(causal, window, block_q, block_k,
                                       interpret))
