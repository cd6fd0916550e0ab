"""Transformer-block zoo.

Every block implements the same protocol so the scanned stack
(models/stack.py) can treat a layer uniformly:

    init(key) -> params
    train(p, x, pos, ctx)              -> (x, aux)          # full-sequence
    cache_spec(batch, cap, dtype)      -> BlockCache
    apply(p, x, pos, cache, ctx, plan=None)
        -> (x, cache, aux, plan)                            # prefill chunk
                                                            # or decode (t=1)

``plan`` is the cross-layer ``core/plan.py::PlanCarry`` (None disables
reuse: every selecting block builds its own plan).  Selecting blocks
additionally implement ``plan_carry_shape(cache, t, method, qcfg)`` so the
stack can decide statically whether a shared carry is geometrically valid.

``ctx`` (dict):
    method     selection method name ("full" = dense attention)
    qcfg       QuokaConfig
    enc_out    whisper encoder output (b, n_ctx, d) — train/cache-build only
    shared     params of the zamba2 shared attention block
    slot       cache write slot of the chunk (traced scalar, or per-row (b,)
               under continuous batching).  Distinct from ``pos``: pad slots
               carry pos == -1 while still occupying a cache slot.  Absent ->
               derived as pos[0, 0] (the legacy unpadded path).
    layer_idx  traced GLOBAL layer index (set by the stack scan when plan
               reuse is on) — drives the reuse_interval/correction schedule.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import plan as plan_mod
from repro.core import selection as sel_mod
from repro.core.attention import (NEG_INF, attention_with_positions,
                                  dense_attention, position_mask)
from repro.kernels import ops as kops
from repro.models import mamba2, moe, rwkv6
from repro.models.layers import (layernorm, layernorm_init, linear,
                                 linear_init, mlp, mlp_init, rmsnorm,
                                 rmsnorm_init, rope)
from repro.serving.cache import (BlockCache, CrossKV, KVCache, LatentCache,
                                 kv_init, kv_write, kv_write_ring,
                                 latent_init, latent_write)
from repro.sharding import ctx as shctx


def _chunk_slot(ctx, pos):
    """Cache write slot for the current chunk: explicit ``ctx["slot"]`` when
    provided (padded prompts / continuous batching), else the first query
    position (slot == position on the legacy unpadded path)."""
    slot = ctx.get("slot") if isinstance(ctx, dict) else None
    return pos[0, 0] if slot is None else slot


def _norm_fns(cfg: ModelConfig):
    if cfg.family == "audio":          # whisper uses LayerNorm
        return layernorm_init, lambda p, x: layernorm(p, x)
    return rmsnorm_init, lambda p, x: rmsnorm(p, x, cfg.norm_eps)


# ============================================================================
# GQA attention block (dense / sliding-window / MoE-FFN / encoder)
# ============================================================================

class AttnBlock:
    def __init__(self, cfg: ModelConfig, kind: str):
        self.cfg = cfg
        self.kind = kind
        self.window = cfg.sliding_window if kind == "attn_local" else None
        self.causal = kind != "enc_attn"
        self.is_moe = kind == "attn_moe"
        self.norm_init, self.norm = _norm_fns(cfg)

    # ---- params ----
    def init(self, key):
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.resolved_head_dim
        ks = jax.random.split(key, 6)
        p = {
            "ln1": self.norm_init(d),
            "wq": linear_init(ks[0], d, cfg.n_heads * hd),
            "wk": linear_init(ks[1], d, cfg.n_kv_heads * hd),
            "wv": linear_init(ks[2], d, cfg.n_kv_heads * hd),
            "wo": linear_init(ks[3], cfg.n_heads * hd, d,
                              std=1.0 / math.sqrt(cfg.n_heads * hd * 2 * cfg.n_layers)),
            "ln2": self.norm_init(d),
        }
        if self.is_moe:
            p["moe"] = moe.moe_init(ks[4], cfg)
        else:
            p["mlp"] = mlp_init(ks[4], d, cfg.d_ff,
                                gated=cfg.act != "gelu")
        return p

    # ---- helpers ----
    def _qkv(self, p, x, pos):
        cfg = self.cfg
        b, t, _ = x.shape
        hd = cfg.resolved_head_dim
        q = linear(p["wq"], x).reshape(b, t, cfg.n_heads, hd)
        k = linear(p["wk"], x).reshape(b, t, cfg.n_kv_heads, hd)
        v = linear(p["wv"], x).reshape(b, t, cfg.n_kv_heads, hd)
        if cfg.use_rope:
            q = rope(q, pos, cfg.rope_theta)
            k = rope(k, pos, cfg.rope_theta)
        return (shctx.shard_heads(q, 2), shctx.shard_heads(k, 2),
                shctx.shard_heads(v, 2))

    def _ffn(self, p, x, ctx):
        cfg = self.cfg
        with jax.named_scope("mlp"):
            h = self.norm(p["ln2"], x)
            if self.is_moe:
                y = moe.moe_apply(p["moe"], h, cfg, ctx)
                aux = ctx.pop("aux_loss", 0.0) if isinstance(ctx, dict) \
                    else 0.0
                return x + y, aux
            return x + mlp(p["mlp"], h, cfg.act), 0.0

    # ---- modes ----
    def train(self, p, x, pos, ctx):
        q, k, v = self._qkv(p, self.norm(p["ln1"], x), pos)
        att = attention_with_positions(q, k, v, pos, pos,
                                       causal=self.causal, window=self.window)
        b, t = x.shape[:2]
        x = x + linear(p["wo"], att.reshape(b, t, -1))
        return self._ffn(p, x, dict(ctx) if ctx else {})

    def cache_spec(self, batch, cap, dtype):
        cfg = self.cfg
        if self.kind == "enc_attn":
            return BlockCache()
        if self.window is not None:
            cap = min(cap, self.window)
        return BlockCache(kv=kv_init(batch, cap, cfg.n_kv_heads,
                                     cfg.resolved_head_dim, dtype))

    def plan_carry_shape(self, cache, t: int, method: str, qcfg):
        """Static ``SelectionPlan.idx`` shape this block would build for a
        t-token chunk (from possibly layer-stacked cache leaves), or None
        when the block never selects (encoder / dense fallback / grid
        mismatch) — which disables the shared cross-layer carry."""
        kv = getattr(cache, "kv", None)
        if self.kind == "enc_attn" or kv is None or kv == ():
            return None
        b, cap, n_kv = kv.k.shape[-4], kv.k.shape[-3], kv.k.shape[-2]
        budget = sel_mod.resolve_budget(qcfg, cap)
        if method == "full" or cap <= budget + t:
            return None
        if plan_mod.grid(qcfg) > 1 and cap % plan_mod.grid(qcfg):
            return None
        return plan_mod.plan_idx_shape(qcfg, b, n_kv, cap, budget)

    def apply(self, p, x, pos, cache: BlockCache, ctx, plan=None):
        """Prefill chunk or decode step (t == chunk size or 1)."""
        cfg = self.cfg
        if self.kind == "enc_attn":
            y, aux = self.train(p, x, pos, ctx)
            return y, cache, aux, plan
        b, t, _ = x.shape
        with jax.named_scope("attn_proj"):
            q, k, v = self._qkv(p, self.norm(p["ln1"], x), pos)
        start = _chunk_slot(ctx, pos)
        kv = cache.kv
        write = kv_write_ring if self.window is not None else kv_write
        kv = write(kv, k, v, start, pos_new=pos)

        method = ctx.get("method", "full")
        budget = sel_mod.resolve_budget(ctx["qcfg"], kv.capacity) \
            if method != "full" else 0
        if method == "full" or kv.capacity <= budget + t:
            att = attention_with_positions(q, kv.k, kv.v, pos, kv.pos,
                                           causal=True, window=self.window)
            if isinstance(ctx, dict) and ctx.get("obs"):
                ctx["_obs"] = plan_mod.dense_obs(kv.pos, start)
        elif plan_mod.fused_route(ctx["qcfg"], method, kv.k,
                                  window=self.window):
            # gather-free path: the plan's block ids drive the kernel's
            # index maps directly — the chunk KV was just written into the
            # cache above, so the kernel reads it from there rather than
            # from a [budget | chunk] concat
            att, plan = plan_mod.fused_attend_with_ctx(
                ctx, plan, method, q, kv.k, kv.v, kv.pos, start,
                ctx["qcfg"], budget=budget, q_valid=pos >= 0)
        else:
            sel, plan = plan_mod.select_with_ctx(
                ctx, plan, method, q, kv.k, kv.v, kv.pos, start,
                ctx["qcfg"], budget=budget, q_valid=pos >= 0)
            att = self._selected_attention(q, k, v, pos, sel,
                                           backend=ctx.get("backend"))
        with jax.named_scope("attn_proj"):
            x = x + linear(p["wo"], att.reshape(b, t, -1))
        x, aux = self._ffn(p, x, dict(ctx) if ctx else {})
        return x, cache._replace(kv=kv), aux, plan

    def _selected_attention(self, q, k_chunk, v_chunk, pos, sel,
                            backend=None):
        """Attention over [selected budget | current chunk] via the kernel
        facade: the budget is an unconditioned prefix (`boundary`), budget
        padding is masked through per-KV-head `k_valid` (sel.pos == -1).

        Sliding-window layers keep the masked dense path — the window
        constraint on selected keys is per-QUERY and cannot be expressed by
        the kernel's static boundary + per-key validity contract.
        """
        b, t = q.shape[:2]
        n_kv = k_chunk.shape[2]
        k_cat = jnp.concatenate([sel.k, k_chunk], axis=1)
        v_cat = jnp.concatenate([sel.v, v_chunk], axis=1)
        # chunk keys with pos == -1 are pad slots — never attendable
        chunk_valid = jnp.broadcast_to((pos >= 0)[:, None, :], (b, n_kv, t))
        if self.window is None:
            k_valid = jnp.concatenate([sel.pos >= 0, chunk_valid], axis=-1)
            return kops.attention(q, k_cat, v_cat, k_valid, causal=True,
                                  boundary=sel.pos.shape[-1],
                                  backend=backend, cfg=self.cfg.quoka)
        qp = pos[:, None, :, None]                       # (b,1,t,1)
        sp = sel.pos[:, :, None, :]                      # (b,n_kv,1,B)
        m_sel = (sp >= 0) & (sp > qp - self.window)
        m_sel = jnp.broadcast_to(m_sel, (b, n_kv, t, sel.pos.shape[-1]))
        tri = jnp.tril(jnp.ones((t, t), bool))
        m_chunk = tri[None, None] & chunk_valid[:, :, None, :]
        m_chunk = jnp.broadcast_to(m_chunk, (b, n_kv, t, t))
        mask = jnp.concatenate([m_sel, m_chunk], axis=-1)
        return dense_attention(q, k_cat, v_cat, mask)


# ============================================================================
# DeepSeek MLA block (absorbed-latent attention; compressed KV cache)
# ============================================================================

class MLABlock:
    def __init__(self, cfg: ModelConfig, kind: str):
        self.cfg = cfg
        self.kind = kind
        self.is_moe = kind == "mla_moe"
        self.norm_init, self.norm = _norm_fns(cfg)
        m = cfg.mla
        self.scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)

    def init(self, key):
        cfg = self.cfg
        m = cfg.mla
        d, h = cfg.d_model, cfg.n_heads
        ks = jax.random.split(key, 8)
        p = {
            "ln1": self.norm_init(d),
            "wq_a": linear_init(ks[0], d, m.q_lora_rank),
            "q_ln": rmsnorm_init(m.q_lora_rank),
            "wq_b": linear_init(ks[1], m.q_lora_rank,
                                h * (m.qk_nope_dim + m.qk_rope_dim)),
            "wkv_a": linear_init(ks[2], d, m.kv_lora_rank + m.qk_rope_dim),
            "kv_ln": rmsnorm_init(m.kv_lora_rank),
            # decompression weights, stored head-major for absorption
            "wk_b": jax.random.normal(ks[3], (m.kv_lora_rank, h, m.qk_nope_dim))
                    / math.sqrt(m.kv_lora_rank),
            "wv_b": jax.random.normal(ks[4], (m.kv_lora_rank, h, m.v_head_dim))
                    / math.sqrt(m.kv_lora_rank),
            "wo": linear_init(ks[5], h * m.v_head_dim, d,
                              std=1.0 / math.sqrt(h * m.v_head_dim * 2 * cfg.n_layers)),
            "ln2": self.norm_init(d),
        }
        if self.is_moe:
            p["moe"] = moe.moe_init(ks[6], cfg)
        else:
            p["mlp"] = mlp_init(ks[6], d, cfg.d_ff)
        return p

    # ---- projections ----
    def _queries(self, p, h, pos):
        cfg, m = self.cfg, self.cfg.mla
        b, t, _ = h.shape
        cq = rmsnorm(p["q_ln"], linear(p["wq_a"], h), cfg.norm_eps)
        q = linear(p["wq_b"], cq).reshape(b, t, cfg.n_heads,
                                          m.qk_nope_dim + m.qk_rope_dim)
        q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
        q_rope = rope(q_rope, pos, cfg.rope_theta)
        # absorbed: q_abs[h] = q_nope[h] @ W_uk[h]  -> latent space
        q_abs = jnp.einsum("bthn,rhn->bthr", q_nope,
                           p["wk_b"].astype(q_nope.dtype))
        return q_abs, q_rope

    def _latent_kv(self, p, h, pos):
        cfg, m = self.cfg, self.cfg.mla
        kv = linear(p["wkv_a"], h)
        ckv = rmsnorm(p["kv_ln"], kv[..., :m.kv_lora_rank], cfg.norm_eps)
        kr = kv[..., m.kv_lora_rank:]
        kr = rope(kr[:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]
        return ckv, kr

    def _absorbed_full(self, p, q_abs, q_rope, ckv, krope, q_pos, k_pos):
        """Full (position-masked) absorbed attention; streams key blocks via
        blocked_attention above the materialisation threshold so the T² score
        matrix never hits HBM (train / dense-prefill / long decode)."""
        from repro.core.attention import BLOCKED_THRESHOLD, blocked_attention
        m = self.cfg.mla
        b, t = q_abs.shape[:2]
        tk = ckv.shape[1]
        if tk > BLOCKED_THRESHOLD:
            qc = jnp.concatenate([q_abs, q_rope], axis=-1)
            kc = jnp.concatenate([ckv, krope], axis=-1)[:, :, None, :]
            o_lat = blocked_attention(qc, kc, ckv[:, :, None, :],
                                      q_pos, k_pos, causal=True,
                                      scale=self.scale)
            out = jnp.einsum("bthr,rhv->bthv", o_lat.astype(jnp.float32),
                             p["wv_b"].astype(jnp.float32))
            return out.reshape(b, t, -1).astype(q_abs.dtype)
        mask = position_mask(q_pos, k_pos, causal=True)
        return self._absorbed_attention(p, q_abs, q_rope, ckv, krope, mask)

    def _absorbed_attention(self, p, q_abs, q_rope, ckv, krope, mask):
        """Attention entirely in latent space (the MLA deployment trick)."""
        m = self.cfg.mla
        logits = (jnp.einsum("bthr,bsr->bhts", q_abs.astype(jnp.float32),
                             ckv.astype(jnp.float32))
                  + jnp.einsum("bthr,bsr->bhts", q_rope.astype(jnp.float32),
                               krope.astype(jnp.float32))) * self.scale
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhts,bsr->bthr", probs, ckv.astype(jnp.float32))
        out = jnp.einsum("bthr,rhv->bthv", o_lat,
                         p["wv_b"].astype(jnp.float32))
        b, t = q_abs.shape[:2]
        return out.reshape(b, t, -1).astype(q_abs.dtype)

    def _ffn(self, p, x, ctx):
        cfg = self.cfg
        h = self.norm(p["ln2"], x)
        if self.is_moe:
            c = dict(ctx) if ctx else {}
            y = moe.moe_apply(p["moe"], h, cfg, c)
            return x + y, c.pop("aux_loss", 0.0)
        return x + mlp(p["mlp"], h, cfg.act), 0.0

    # ---- modes ----
    def train(self, p, x, pos, ctx):
        h = self.norm(p["ln1"], x)
        q_abs, q_rope = self._queries(p, h, pos)
        ckv, kr = self._latent_kv(p, h, pos)
        att = self._absorbed_full(p, q_abs, q_rope, ckv, kr, pos, pos)
        x = x + linear(p["wo"], att)
        return self._ffn(p, x, ctx)

    def cache_spec(self, batch, cap, dtype):
        m = self.cfg.mla
        return BlockCache(latent=latent_init(batch, cap, m.kv_lora_rank,
                                             m.qk_rope_dim, dtype))

    def plan_carry_shape(self, cache, t: int, method: str, qcfg):
        """Latent selection geometry: one shared 'KV head' (n_kv == 1)."""
        lat = getattr(cache, "latent", None)
        if lat is None or lat == ():
            return None
        b, cap = lat.ckv.shape[-3], lat.ckv.shape[-2]
        budget = sel_mod.resolve_budget(qcfg, cap)
        if method == "full" or cap <= budget + t:
            return None
        if plan_mod.grid(qcfg) > 1 and cap % plan_mod.grid(qcfg):
            return None
        return plan_mod.plan_idx_shape(qcfg, b, 1, cap, budget)

    def apply(self, p, x, pos, cache: BlockCache, ctx, plan=None):
        cfg, m = self.cfg, self.cfg.mla
        b, t, _ = x.shape
        h = self.norm(p["ln1"], x)
        q_abs, q_rope = self._queries(p, h, pos)
        ckv, kr = self._latent_kv(p, h, pos)
        start = _chunk_slot(ctx, pos)
        lat = latent_write(cache.latent, ckv, kr, start, pos_new=pos)

        method = ctx.get("method", "full")
        budget = sel_mod.resolve_budget(ctx["qcfg"], lat.capacity) \
            if method != "full" else 0
        if method == "full" or lat.capacity <= budget + t:
            att = self._absorbed_full(p, q_abs, q_rope, lat.ckv,
                                      lat.krope, pos, lat.pos)
            if isinstance(ctx, dict) and ctx.get("obs"):
                ctx["_obs"] = plan_mod.dense_obs(lat.pos, start)
        else:
            att, plan = self._selected_attention(p, q_abs, q_rope, ckv, kr,
                                                 pos, lat, start, ctx, plan)
        x = x + linear(p["wo"], att)
        x, aux = self._ffn(p, x, ctx)
        return x, cache._replace(latent=lat), aux, plan

    def _selected_attention(self, p, q_abs, q_rope, ckv_chunk, kr_chunk,
                            pos, lat: LatentCache, start, ctx, plan=None):
        """QUOKA (or baseline) on the COMPRESSED latent: one shared 'KV head'
        per token — scoring queries are the absorbed per-head queries, so
        pre-aggregation averages over all n_heads (n_kv == 1).

        The post-selection attention runs through the kernel facade in
        latent space: queries/keys are the concatenated [absorbed | rope]
        vectors, values are the latent ckv zero-padded to the key width
        (a zero value-tail does not change the softmax; the padded output
        columns are sliced off before the W_uv decompression)."""
        b, t = q_abs.shape[:2]
        qc = ctx["qcfg"]
        method = ctx.get("method", "quoka")
        latent_keys = jnp.concatenate([lat.ckv, lat.krope],
                                      axis=-1)[:, :, None, :]   # (b,T,1,r+rd)
        q_score = jnp.concatenate([q_abs, q_rope], axis=-1)      # (b,t,h,·)
        sel, plan = plan_mod.select_with_ctx(
            ctx, plan, method, q_score, latent_keys, latent_keys, lat.pos,
            start, qc, q_valid=pos >= 0)
        r = self.cfg.mla.kv_lora_rank
        ckv_sel, kr_sel = sel.k[..., 0, :r], sel.k[..., 0, r:]   # (b,B,·)
        ckv_cat = jnp.concatenate([ckv_sel, ckv_chunk], axis=1)
        kr_cat = jnp.concatenate([kr_sel, kr_chunk], axis=1)
        k_cat = jnp.concatenate([ckv_cat, kr_cat], axis=-1)[:, :, None, :]
        rd = k_cat.shape[-1] - r
        v_pad = jnp.pad(ckv_cat, ((0, 0), (0, 0), (0, rd)))[:, :, None, :]
        k_valid = jnp.concatenate(
            [sel.pos >= 0, (pos >= 0)[:, None, :]], axis=-1)
        o_lat = kops.attention(q_score, k_cat, v_pad, k_valid, causal=True,
                               boundary=sel.pos.shape[-1], scale=self.scale,
                               backend=ctx.get("backend"), cfg=qc)[..., :r]
        out = jnp.einsum("bthr,rhv->bthv", o_lat.astype(jnp.float32),
                         p["wv_b"].astype(jnp.float32))
        return out.reshape(b, t, -1).astype(q_abs.dtype), plan


# ============================================================================
# Mamba2 block (optionally followed by the zamba2 shared attention block)
# ============================================================================

class MambaBlock:
    def __init__(self, cfg: ModelConfig, kind: str):
        self.cfg = cfg
        self.kind = kind
        self.with_shared = kind == "mamba_shared_attn"
        self.norm_init, self.norm = _norm_fns(cfg)
        if self.with_shared:
            self.shared = AttnBlock(cfg, "attn")

    def init(self, key):
        return {"ln": self.norm_init(self.cfg.d_model),
                "mamba": mamba2.mamba_init(key, self.cfg)}

    def cache_spec(self, batch, cap, dtype):
        mc = mamba2.mamba_cache_init(batch, self.cfg, dtype)
        if self.with_shared:
            kvc = self.shared.cache_spec(batch, cap, dtype)
            return BlockCache(mamba=mc, kv=kvc.kv)
        return BlockCache(mamba=mc)

    def train(self, p, x, pos, ctx):
        cache = mamba2.mamba_cache_init(x.shape[0], self.cfg, x.dtype)
        y, _ = mamba2.mamba_apply(p["mamba"], self.norm(p["ln"], x),
                                  cache, self.cfg)
        x = x + y
        aux = 0.0
        if self.with_shared:
            x, aux = self.shared.train(ctx["shared"], x, pos, ctx)
        return x, aux

    def plan_carry_shape(self, cache, t: int, method: str, qcfg):
        if not self.with_shared:
            return None
        return self.shared.plan_carry_shape(cache, t, method, qcfg)

    def apply(self, p, x, pos, cache: BlockCache, ctx, plan=None):
        y, mc = mamba2.mamba_apply(p["mamba"], self.norm(p["ln"], x),
                                   cache.mamba, self.cfg)
        x = x + y
        aux = 0.0
        if self.with_shared:
            x, kvc, aux, plan = self.shared.apply(ctx["shared"], x, pos,
                                                  BlockCache(kv=cache.kv),
                                                  ctx, plan=plan)
            return x, cache._replace(mamba=mc, kv=kvc.kv), aux, plan
        return x, cache._replace(mamba=mc), aux, plan


# ============================================================================
# RWKV6 block — unified segment apply (train == prefill with fresh state)
# ============================================================================

class RWKVBlock:
    def __init__(self, cfg: ModelConfig, kind: str = "rwkv"):
        self.cfg = cfg
        self.kind = "rwkv"
        self.norm_init, self.norm = _norm_fns(cfg)

    def init(self, key):
        p = rwkv6.rwkv_init(key, self.cfg)
        p["ln1"] = self.norm_init(self.cfg.d_model)
        p["ln2"] = self.norm_init(self.cfg.d_model)
        return p

    def cache_spec(self, batch, cap, dtype):
        return BlockCache(rwkv=rwkv6.rwkv_cache_init(batch, self.cfg, dtype))

    def train(self, p, x, pos, ctx):
        cache = rwkv6.rwkv_cache_init(x.shape[0], self.cfg, x.dtype)
        y, _, _ = self._run(p, x, cache)
        return y, 0.0

    def apply(self, p, x, pos, cache: BlockCache, ctx, plan=None):
        y, new, _ = self._run(p, x, cache.rwkv)
        return y, cache._replace(rwkv=new), 0.0, plan

    def _run(self, p, x, rc):
        y, sh_tm, wkv = rwkv6.time_mix(p["tm"], self.norm(p["ln1"], x),
                                       rc.shift_tm, rc.wkv, self.cfg)
        x = x + y
        y, sh_cm = rwkv6.channel_mix(p["cm"], self.norm(p["ln2"], x),
                                     rc.shift_cm)
        x = x + y
        new = rc._replace(shift_tm=sh_tm.astype(rc.shift_tm.dtype),
                          shift_cm=sh_cm.astype(rc.shift_cm.dtype), wkv=wkv)
        return x, new, 0.0


# ============================================================================
# Whisper decoder block: causal self-attn + cross-attn + MLP
# ============================================================================

class DecCrossBlock:
    def __init__(self, cfg: ModelConfig, kind: str = "dec_cross"):
        self.cfg = cfg
        self.kind = "dec_cross"
        self.norm_init, self.norm = _norm_fns(cfg)
        self.self_attn = AttnBlock(cfg, "attn")   # reuse qkv/selection logic

    def init(self, key):
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.resolved_head_dim
        ks = jax.random.split(key, 8)
        return {
            "self": self.self_attn.init(ks[0]),     # ln1/wq/wk/wv/wo/ln2/mlp
            "ln_x": self.norm_init(d),
            "xq": linear_init(ks[1], d, cfg.n_heads * hd),
            "xk": linear_init(ks[2], d, cfg.n_kv_heads * hd),
            "xv": linear_init(ks[3], d, cfg.n_kv_heads * hd),
            "xo": linear_init(ks[4], cfg.n_heads * hd, d),
        }

    def cache_spec(self, batch, cap, dtype):
        cfg = self.cfg
        base = self.self_attn.cache_spec(batch, cap, dtype)
        n_ctx = cfg.encoder.n_ctx
        cross = CrossKV(
            k=jnp.zeros((batch, n_ctx, cfg.n_kv_heads,
                         cfg.resolved_head_dim), dtype),
            v=jnp.zeros((batch, n_ctx, cfg.n_kv_heads,
                         cfg.resolved_head_dim), dtype))
        return base._replace(cross=cross)

    def build_cross(self, p, enc_out) -> CrossKV:
        cfg = self.cfg
        b, s, _ = enc_out.shape
        hd = cfg.resolved_head_dim
        k = linear(p["xk"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
        v = linear(p["xv"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
        return CrossKV(k=k, v=v)

    def _cross(self, p, x, cross: CrossKV):
        cfg = self.cfg
        b, t, _ = x.shape
        hd = cfg.resolved_head_dim
        h = self.norm(p["ln_x"], x)
        q = linear(p["xq"], h).reshape(b, t, cfg.n_heads, hd)
        att = dense_attention(q, cross.k, cross.v)      # non-causal
        return x + linear(p["xo"], att.reshape(b, t, -1))

    def train(self, p, x, pos, ctx):
        # self attention sub-block (with its own MLP) then cross attention
        sp = dict(p["self"])
        mlp_p, ln2 = sp["mlp"], sp["ln2"]
        x, _, _ = self._self_only(sp, x, pos, ctx, train=True)
        cross = self.build_cross(p, ctx["enc_out"])
        x = self._cross(p, x, cross)
        x = x + mlp(mlp_p, self.norm(ln2, x), self.cfg.act)
        return x, 0.0

    def _self_only(self, sp, x, pos, ctx, train: bool, cache=None,
                   plan=None):
        """Self-attention + residual, WITHOUT the MLP of AttnBlock."""
        a = self.self_attn
        q, k, v = a._qkv(sp, self.norm(sp["ln1"], x), pos)
        b, t = x.shape[:2]
        if train:
            att = attention_with_positions(q, k, v, pos, pos, causal=True)
            return x + linear(sp["wo"], att.reshape(b, t, -1)), None, plan
        start = _chunk_slot(ctx, pos)
        kv = kv_write(cache, k, v, start, pos_new=pos)
        method = ctx.get("method", "full")
        budget = sel_mod.resolve_budget(ctx["qcfg"], kv.capacity) \
            if method != "full" else 0
        if method == "full" or kv.capacity <= budget + t:
            att = attention_with_positions(q, kv.k, kv.v, pos, kv.pos,
                                           causal=True)
            if isinstance(ctx, dict) and ctx.get("obs"):
                ctx["_obs"] = plan_mod.dense_obs(kv.pos, start)
        elif plan_mod.fused_route(ctx["qcfg"], method, kv.k):
            att, plan = plan_mod.fused_attend_with_ctx(
                ctx, plan, method, q, kv.k, kv.v, kv.pos, start,
                ctx["qcfg"], budget=budget, q_valid=pos >= 0)
        else:
            s, plan = plan_mod.select_with_ctx(
                ctx, plan, method, q, kv.k, kv.v, kv.pos, start,
                ctx["qcfg"], budget=budget, q_valid=pos >= 0)
            att = a._selected_attention(q, k, v, pos, s,
                                        backend=ctx.get("backend"))
        return x + linear(sp["wo"], att.reshape(b, t, -1)), kv, plan

    def plan_carry_shape(self, cache, t: int, method: str, qcfg):
        return self.self_attn.plan_carry_shape(cache, t, method, qcfg)

    def apply(self, p, x, pos, cache: BlockCache, ctx, plan=None):
        sp = p["self"]
        x, kv, plan = self._self_only(sp, x, pos, ctx, train=False,
                                      cache=cache.kv, plan=plan)
        x = self._cross(p, x, cache.cross)
        x = x + mlp(sp["mlp"], self.norm(sp["ln2"], x), self.cfg.act)
        return x, cache._replace(kv=kv), 0.0, plan


# ============================================================================

_KINDS = {
    "attn": AttnBlock, "attn_local": AttnBlock, "attn_moe": AttnBlock,
    "enc_attn": AttnBlock,
    "mla": MLABlock, "mla_moe": MLABlock,
    "mamba": MambaBlock, "mamba_shared_attn": MambaBlock,
    "rwkv": RWKVBlock,
    "dec_cross": DecCrossBlock,
}


def make_block(cfg: ModelConfig, kind: str):
    return _KINDS[kind](cfg, kind)
