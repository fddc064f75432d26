"""Unified Backbone covering all six assigned architecture families.

One composable model definition, driven entirely by :class:`ArchConfig`:

* dense / vlm / audio : uniform [ln, attn, ln, SwiGLU] blocks
* moe                 : same, FFN replaced by MoE (optional leading dense layers)
* ssm                 : uniform [ln, mamba2-SSD] blocks
* hybrid (zamba2)     : groups of SSM blocks + a periodically applied *shared*
                        attention/MLP block (one param set reused at each site)
* dit (flux-like)     : bidirectional blocks with adaLN-zero time/cond
                        modulation — the paper's own model family

Layers are stacked and driven by ``lax.scan`` so compile time and HLO size are
O(1) in depth.  Three entry points: ``forward`` (train), ``prefill``,
``decode`` (one token against a KV/state cache).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding as shlib
from repro.config import ArchConfig
from repro.models import attention, layers, mla, moe, ssm
from repro.models.params import P, axes_tree, stack

F32 = jnp.float32

ATTN_FAMILIES = ("dense", "moe", "vlm", "audio", "dit")


# ---------------------------------------------------------------------------
# Block specs
# ---------------------------------------------------------------------------

def _attn_spec(cfg: ArchConfig) -> Dict:
    return mla.spec(cfg) if cfg.mla else attention.spec(cfg)


def _attn_block_spec(cfg: ArchConfig, ffn: str) -> Dict:
    d = cfg.d_model
    s = {
        "ln1": layers.rmsnorm_spec(d),
        "attn": _attn_spec(cfg),
        "ln2": layers.rmsnorm_spec(d),
    }
    s["ffn"] = moe.spec(cfg) if ffn == "moe" else layers.mlp_spec(d, cfg.d_ff)
    if cfg.family == "dit":
        # adaLN-zero: cond vector -> 6 modulation params per block
        s["ada"] = P((d, 6 * d), ("embed", None), "zeros")
    return s


def _ssm_block_spec(cfg: ArchConfig) -> Dict:
    return {"ln": layers.rmsnorm_spec(cfg.d_model), "ssm": ssm.spec(cfg)}


class Backbone:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.n_prefix = cfg.frontend.n_tokens
        # logical-axes trees of the UNSTACKED block specs — used by the
        # weight-gathered FSDP constraint inside scan bodies (sharding.py)
        self._axes_mlp = axes_tree(_attn_block_spec(cfg, "mlp"))
        self._axes_moe = (axes_tree(_attn_block_spec(cfg, "moe"))
                          if cfg.moe and cfg.moe.n_experts else None)
        self._axes_ssm = (axes_tree(_ssm_block_spec(cfg))
                          if cfg.family in ("ssm", "hybrid") else None)

    def _gather(self, blk_p: Dict) -> Dict:
        """Constrain a sliced block's weights to the gathered layout."""
        if "ssm" in blk_p:
            return shlib.constrain_params(blk_p, self._axes_ssm)
        if "router" in blk_p.get("ffn", {}):
            return shlib.constrain_params(blk_p, self._axes_moe)
        return shlib.constrain_params(blk_p, self._axes_mlp)

    # ------------------------------------------------------------------ spec
    def spec(self) -> Dict:
        cfg = self.cfg
        d = cfg.d_model
        s: Dict[str, Any] = {
            "embed": P((cfg.vocab_size, d), ("vocab", "embed"), "small"),
            "final_norm": layers.rmsnorm_spec(d),
        }
        if not cfg.tie_embeddings:
            s["lm_head"] = P((d, cfg.vocab_size), ("embed", "vocab"))
        if cfg.frontend.kind != "none":
            s["frontend_proj"] = P((cfg.frontend.embed_dim, d),
                                   (None, "embed"))
        fam = cfg.family
        if fam == "ssm":
            s["blocks"] = stack(_ssm_block_spec(cfg), cfg.n_layers)
        elif fam == "hybrid":
            hy = cfg.hybrid
            n_groups = cfg.n_layers // hy.attn_every
            inner = stack(_ssm_block_spec(cfg), hy.attn_every, None)
            s["blocks"] = stack(inner, n_groups, "groups")
            s["shared_attn"] = _attn_block_spec(cfg, "mlp")
        elif fam in ("moe",):
            fk = cfg.moe.first_k_dense
            if fk:
                s["dense_blocks"] = stack(_attn_block_spec(cfg, "mlp"), fk)
            s["blocks"] = stack(_attn_block_spec(cfg, "moe"),
                                cfg.n_layers - fk)
        else:  # dense / vlm / audio / dit
            s["blocks"] = stack(_attn_block_spec(cfg, "mlp"), cfg.n_layers)
        return s

    # ----------------------------------------------------------- block apply
    def _attn_block(self, p: Dict, x: jax.Array, *, causal: bool, window: int,
                    positions: jax.Array, cond: Optional[jax.Array],
                    return_cache: bool) -> Tuple[jax.Array, Any, Dict]:
        cfg = self.cfg
        p = self._gather(p)
        aux: Dict[str, jax.Array] = {}
        if cfg.family == "dit" and cond is not None:
            # named scopes (HLO op_name metadata only) let a profile split
            # the block into its modulation, attention and MLP
            with jax.named_scope("dit_adaln"):
                mod = jnp.einsum("bd,de->be", cond, p["ada"],
                                 preferred_element_type=F32).astype(x.dtype)
                (sh_a, sc_a, g_a, sh_m, sc_m, g_m) = jnp.split(mod, 6,
                                                               axis=-1)
                h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
                h = h * (1 + sc_a[:, None]) + sh_a[:, None]
            attn_fn = mla.apply_full if cfg.mla else attention.apply_full
            with jax.named_scope("dit_attention"):
                a_out, cache = attn_fn(p["attn"], cfg, h, causal=causal,
                                       window=window, positions=positions,
                                       return_cache=return_cache)
            with jax.named_scope("dit_adaln"):
                x = x + g_a[:, None] * a_out
                h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
                h = h * (1 + sc_m[:, None]) + sh_m[:, None]
            with jax.named_scope("dit_mlp"):
                f_out = layers.mlp(p["ffn"], h)
            with jax.named_scope("dit_adaln"):
                x = x + g_m[:, None] * f_out
            return x, cache, aux
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        attn_fn = mla.apply_full if cfg.mla else attention.apply_full
        a_out, cache = attn_fn(p["attn"], cfg, h, causal=causal, window=window,
                               positions=positions, return_cache=return_cache)
        x = x + a_out
        h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if "router" in p["ffn"]:
            f_out, aux = moe.apply(p["ffn"], cfg, h)
        else:
            f_out = layers.mlp(p["ffn"], h)
        x = x + f_out
        return x, cache, aux

    def _attn_block_decode(self, p: Dict, x: jax.Array, cache, pos,
                           *, window: int) -> Tuple[jax.Array, Any]:
        cfg = self.cfg
        p = self._gather(p)
        h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
        dec_fn = mla.apply_decode if cfg.mla else attention.apply_decode
        a_out, cache = dec_fn(p["attn"], cfg, h, cache, pos, window=window)
        x = x + a_out
        h = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if "router" in p["ffn"]:
            f_out, _ = moe.apply(p["ffn"], cfg, h)
        else:
            f_out = layers.mlp(p["ffn"], h)
        return x + f_out, cache

    def _ssm_block(self, p: Dict, x: jax.Array, *, return_cache: bool
                   ) -> Tuple[jax.Array, Any]:
        p = self._gather(p)
        h = layers.rmsnorm(p["ln"], x, self.cfg.norm_eps)
        out, cache = ssm.apply_full(p["ssm"], self.cfg, h,
                                    return_cache=return_cache)
        return x + out, cache

    def _ssm_block_decode(self, p: Dict, x: jax.Array, cache
                          ) -> Tuple[jax.Array, Any]:
        p = self._gather(p)
        h = layers.rmsnorm(p["ln"], x, self.cfg.norm_eps)
        out, cache = ssm.apply_decode(p["ssm"], self.cfg, h, cache)
        return x + out, cache

    # ------------------------------------------------------------- embedding
    def embed_inputs(self, params: Dict, tokens: jax.Array,
                     prefix_embed: Optional[jax.Array] = None) -> jax.Array:
        emb = shlib.constrain_params(params["embed"], ("vocab", "embed"))
        x = jnp.take(emb, tokens, axis=0)
        x = shlib.constrain_act(x, ("batch", "seq", "embed"))
        if prefix_embed is not None:
            pe = jnp.einsum("bne,ed->bnd", prefix_embed.astype(x.dtype),
                            params["frontend_proj"],
                            preferred_element_type=F32).astype(x.dtype)
            x = jnp.concatenate([pe, x], axis=1)
        return x

    def logits(self, params: Dict, hidden: jax.Array) -> jax.Array:
        head = self.head_matrix(params)
        return jnp.einsum("...d,dv->...v", hidden, head,
                          preferred_element_type=F32)

    def head_matrix(self, params: Dict) -> jax.Array:
        if self.cfg.tie_embeddings:
            head = params["embed"].T
            return shlib.constrain_params(head, ("embed", "vocab"))
        return shlib.constrain_params(params["lm_head"], ("embed", "vocab"))

    # ------------------------------------------------------- full-seq driver
    def forward_embeds(self, params: Dict, x: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       cond: Optional[jax.Array] = None,
                       remat: bool = False, return_caches: bool = False
                       ) -> Tuple[jax.Array, Any, Dict]:
        """Run all blocks over embedded inputs x: (B, S, d).

        Returns (hidden, caches_or_None, aux_losses).
        """
        cfg = self.cfg
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        aux_tot: Dict[str, jax.Array] = {}

        def add_aux(aux):
            for k, v in aux.items():
                aux_tot[k] = aux_tot.get(k, 0.0) + v

        fam = cfg.family

        if fam == "ssm":
            def body(h, blk_p):
                h, cache = self._ssm_block(blk_p, h,
                                           return_cache=return_caches)
                return h, cache
            if remat:
                body = jax.checkpoint(body)
            x, caches = jax.lax.scan(body, x, params["blocks"])
            x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            return x, caches, aux_tot

        if fam == "hybrid":
            shared_p = params["shared_attn"]

            def group_body(h, grp_p):
                def inner(h2, blk_p):
                    h2, c = self._ssm_block(blk_p, h2,
                                            return_cache=return_caches)
                    return h2, c
                h, ssm_caches = jax.lax.scan(inner, h, grp_p)
                h, attn_cache, _ = self._attn_block(
                    shared_p, h, causal=causal, window=window,
                    positions=positions, cond=cond,
                    return_cache=return_caches)
                return h, (ssm_caches, attn_cache)
            if remat:
                group_body = jax.checkpoint(group_body)
            x, caches = jax.lax.scan(group_body, x, params["blocks"])
            x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            return x, caches, aux_tot

        # attention families
        def body(h, blk_p):
            h, cache, aux = self._attn_block(
                blk_p, h, causal=causal, window=window, positions=positions,
                cond=cond, return_cache=return_caches)
            return h, (cache, aux)
        if remat:
            body = jax.checkpoint(body)

        caches_d = None
        if fam == "moe" and cfg.moe.first_k_dense:
            def body_d(h, blk_p):
                h, cache, aux = self._attn_block(
                    blk_p, h, causal=causal, window=window,
                    positions=positions, cond=cond,
                    return_cache=return_caches)
                return h, (cache, aux)
            if remat:
                body_d = jax.checkpoint(body_d)
            x, (caches_d, _) = jax.lax.scan(body_d, x, params["dense_blocks"])

        x, (caches, auxs) = jax.lax.scan(body, x, params["blocks"])
        add_aux({k: v.sum() for k, v in auxs.items()})
        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        all_caches = ((caches_d, caches) if caches_d is not None else caches)
        return x, all_caches, aux_tot

    # --------------------------------------------------------- decode driver
    def decode_embeds(self, params: Dict, x: jax.Array, caches, pos,
                      *, window: int = 0) -> Tuple[jax.Array, Any]:
        """One-token step. x: (B, 1, d); caches as returned by prefill /
        init_cache; pos: scalar absolute position of the new token."""
        cfg = self.cfg
        fam = cfg.family

        if fam == "ssm":
            def body(h, xs):
                blk_p, cache = xs
                h, cache = self._ssm_block_decode(blk_p, h, cache)
                return h, cache
            x, caches = jax.lax.scan(body, x, (params["blocks"], caches))
            x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            return x, caches

        if fam == "hybrid":
            shared_p = params["shared_attn"]

            def group_body(h, xs):
                grp_p, (ssm_caches, attn_cache) = xs

                def inner(h2, xs2):
                    blk_p, c = xs2
                    h2, c = self._ssm_block_decode(blk_p, h2, c)
                    return h2, c
                h, ssm_caches = jax.lax.scan(inner, h, (grp_p, ssm_caches))
                h, attn_cache = self._attn_block_decode(
                    shared_p, h, attn_cache, pos, window=window)
                return h, (ssm_caches, attn_cache)
            x, caches = jax.lax.scan(group_body, x, (params["blocks"], caches))
            x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            return x, caches

        def body(h, xs):
            blk_p, cache = xs
            h, cache = self._attn_block_decode(blk_p, h, cache, pos,
                                               window=window)
            return h, cache

        if fam == "moe" and cfg.moe.first_k_dense:
            caches_d, caches_m = caches

            def body_d(h, xs):
                blk_p, cache = xs
                h, cache = self._attn_block_decode(blk_p, h, cache, pos,
                                                   window=window)
                return h, cache
            x, caches_d = jax.lax.scan(body_d, x,
                                       (params["dense_blocks"], caches_d))
            x, caches_m = jax.lax.scan(body, x, (params["blocks"], caches_m))
            x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            return x, (caches_d, caches_m)

        x, caches = jax.lax.scan(body, x, (params["blocks"], caches))
        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, caches

    # ----------------------------------------------------------- cache specs
    def cache_specs(self, batch: int, cache_len: int) -> Any:
        """Pytree of (shape, logical_axes) matching the decode cache
        structure (used for zeros-init and for dry-run ShapeDtypeStructs)."""
        cfg = self.cfg
        fam = cfg.family

        def attn_cache_spec(lead: Tuple[int, ...] = ()):
            la = ("layers",) * len(lead)
            if cfg.mla:
                shp = mla.init_cache_shapes(cfg, batch, cache_len)
                return mla.MLACache(
                    c_kv=(lead + shp["c_kv"][0], la + shp["c_kv"][1]),
                    k_rope=(lead + shp["k_rope"][0], la + shp["k_rope"][1]))
            shape, axes = attention.init_cache_shape(cfg, batch, cache_len)
            return attention.KVCache(k=(lead + shape, la + axes),
                                     v=(lead + shape, la + axes))

        def ssm_cache_spec(lead: Tuple[int, ...] = ()):
            la = ("layers",) * len(lead)
            shp = ssm.init_cache_shapes(cfg, batch)
            return ssm.SSMCache(
                conv=(lead + shp["conv"][0], la + shp["conv"][1]),
                state=(lead + shp["state"][0], la + shp["state"][1]))

        if fam == "ssm":
            return ssm_cache_spec((cfg.n_layers,))
        if fam == "hybrid":
            hy = cfg.hybrid
            n_groups = cfg.n_layers // hy.attn_every
            return (ssm_cache_spec((n_groups, hy.attn_every)),
                    attn_cache_spec((n_groups,)))
        if fam == "moe" and cfg.moe.first_k_dense:
            fk = cfg.moe.first_k_dense
            return (attn_cache_spec((fk,)),
                    attn_cache_spec((cfg.n_layers - fk,)))
        return attn_cache_spec((cfg.n_layers,))
