"""Sequence-mixer correctness: chunked scans vs naive recurrences; MLA
absorbed vs explicit; MoE dispatch equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                QuokaConfig, RWKVConfig, SSMConfig)
from repro.models import mamba2, moe, rwkv6
from repro.models.blocks import MLABlock
from repro.serving.cache import MambaCache, RWKVCache

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# RWKV6: chunked parallel form == naive per-token recurrence
# ---------------------------------------------------------------------------

def _naive_rwkv(r, k, v, lw, u, state):
    """o_t = r_t (S_{t-1} + (u*k_t) v_t^T);  S_t = diag(w_t) S + k_t v_t^T."""
    b, t, h, d = r.shape
    outs = []
    S = np.asarray(state, np.float64)
    rn, kn, vn = (np.asarray(x, np.float64) for x in (r, k, v))
    wn = np.exp(np.asarray(lw, np.float64))
    un = np.asarray(u, np.float64)
    for i in range(t):
        bonus = np.einsum("bhd,bhe->bhde", un[None] * kn[:, i], vn[:, i])
        o = np.einsum("bhd,bhde->bhe", rn[:, i], S + bonus)
        outs.append(o)
        S = wn[:, i][..., None] * S + np.einsum(
            "bhd,bhe->bhde", kn[:, i], vn[:, i])
    return np.stack(outs, axis=1), S


def test_rwkv_chunked_matches_naive():
    b, t, h, d = 2, 37, 2, 8          # non-multiple of CHUNK on purpose
    r = jax.random.normal(KEY, (b, t, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, h, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, h, d))
    lw = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 3),
                                    (b, t, h, d)) - 1.0)
    u = jax.random.normal(jax.random.fold_in(KEY, 4), (h, d)) * 0.1
    S0 = jax.random.normal(jax.random.fold_in(KEY, 5), (b, h, d, d)) * 0.1

    # pad to CHUNK multiple like time_mix does
    pad = (-t) % rwkv6.CHUNK
    zf = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out, S = rwkv6._time_mix_chunked(
        zf(r.astype(jnp.float32)), zf(k.astype(jnp.float32)),
        zf(v.astype(jnp.float32)), zf(lw.astype(jnp.float32)),
        u, S0.astype(jnp.float32))
    want, S_want = _naive_rwkv(r, k, v, lw, u, S0)
    np.testing.assert_allclose(np.asarray(out)[:, :t], want,
                               atol=1e-3, rtol=1e-3)


def test_rwkv_state_carry_equals_full_segment():
    """Processing [x1; x2] in two calls with carried cache == one call."""
    cfg = get_config("rwkv6-1.6b").smoke()
    p = rwkv6.rwkv_init(KEY, cfg)
    b, t, d = 2, 64, cfg.d_model
    x = jax.random.normal(KEY, (b, t, d))
    c0 = rwkv6.rwkv_cache_init(b, cfg, jnp.float32)
    y_full, _, _ = rwkv6.time_mix(p["tm"], x, c0.shift_tm, c0.wkv, cfg)
    y1, sh1, wkv1 = rwkv6.time_mix(p["tm"], x[:, :32], c0.shift_tm, c0.wkv, cfg)
    y2, _, _ = rwkv6.time_mix(p["tm"], x[:, 32:], sh1, wkv1, cfg)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:]), np.asarray(y2),
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# Mamba2 SSD: chunked form == naive recurrence; segment carry consistency
# ---------------------------------------------------------------------------

def _naive_ssd(x, dt, la, B, C, state):
    b, t, h, p = x.shape
    S = np.asarray(state, np.float64)
    xs, dts, Bs, Cs = (np.asarray(a, np.float64) for a in (x, dt, B, C))
    an = np.exp(np.asarray(la, np.float64))
    ys = []
    for i in range(t):
        S = an[:, i][:, :, None, None] * S + np.einsum(
            "bh,bhp,bn->bhpn", dts[:, i], xs[:, i], Bs[:, i])
        ys.append(np.einsum("bhpn,bn->bhp", S, Cs[:, i]))
    return np.stack(ys, axis=1), S


def test_mamba_chunked_matches_naive():
    b, t, h, p, n = 2, 70, 2, 4, 8
    x = jax.random.normal(KEY, (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, h)))
    la = -dt * 0.5
    B = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, n))
    C = jax.random.normal(jax.random.fold_in(KEY, 3), (b, t, n))
    S0 = jnp.zeros((b, h, p, n))
    pad = (-t) % mamba2.CHUNK
    pf = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    y, S = mamba2._ssd_chunked(pf(x), pf(dt), pf(la), pf(B), pf(C), S0)
    want, _ = _naive_ssd(x, dt, la, B, C, S0)
    np.testing.assert_allclose(np.asarray(y[:, :t]), want,
                               atol=1e-3, rtol=1e-3)


def test_mamba_segment_carry():
    cfg = get_config("zamba2-7b").smoke()
    p = mamba2.mamba_init(KEY, cfg)
    b, t = 2, 64
    x = jax.random.normal(KEY, (b, t, cfg.d_model))
    c0 = mamba2.mamba_cache_init(b, cfg, jnp.float32)
    y_full, _ = mamba2.mamba_apply(p, x, c0, cfg)
    y1, c1 = mamba2.mamba_apply(p, x[:, :32], c0, cfg)
    y2, _ = mamba2.mamba_apply(p, x[:, 32:], c1, cfg)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:]), np.asarray(y2),
                               atol=2e-3, rtol=2e-3)
    # decode: one token at a time must agree too
    y3, c3 = mamba2.mamba_apply(p, x[:, 32:33], c1, cfg)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:33]), np.asarray(y3),
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# MoE: capacity dispatch ≈ dense gating when nothing is dropped
# ---------------------------------------------------------------------------

def test_moe_capacity_matches_dense_at_high_capacity():
    cfg_d = get_config("olmoe-1b-7b").smoke()
    e = dataclasses.replace(cfg_d.moe, dispatch="dense")
    cfg_dense = dataclasses.replace(cfg_d, moe=e)
    e2 = dataclasses.replace(cfg_d.moe, dispatch="capacity",
                             capacity_factor=float(cfg_d.moe.n_experts))
    cfg_cap = dataclasses.replace(cfg_d, moe=e2)
    p = moe.moe_init(KEY, cfg_dense)
    x = jax.random.normal(KEY, (2, 16, cfg_d.d_model))
    y_dense = moe.moe_apply(p, x, cfg_dense, {})
    y_cap = moe.moe_apply(p, x, cfg_cap, {})
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_cap),
                               atol=2e-4, rtol=2e-4)


def test_moe_aux_loss_accumulates():
    cfg = get_config("olmoe-1b-7b").smoke()
    p = moe.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    ctx = {}
    moe.moe_apply(p, x, cfg, ctx)
    assert "aux_loss" in ctx and float(ctx["aux_loss"]) > 0


# ---------------------------------------------------------------------------
# MLA: absorbed latent attention == explicit decompressed attention
# ---------------------------------------------------------------------------

def test_mla_absorbed_equals_explicit():
    cfg = get_config("deepseek-v3-671b").smoke()
    blk = MLABlock(cfg, "mla")
    p = blk.init(KEY)
    b, t = 2, 32
    x = jax.random.normal(KEY, (b, t, cfg.d_model)) * 0.1
    pos = jnp.arange(t)[None].repeat(b, 0)
    h = blk.norm(p["ln1"], x)
    q_abs, q_rope = blk._queries(p, h, pos)
    ckv, kr = blk._latent_kv(p, h, pos)
    from repro.core.attention import position_mask
    mask = position_mask(pos, pos, causal=True)
    got = blk._absorbed_attention(p, q_abs, q_rope, ckv, kr, mask)
    # explicit: decompress k/v per head, standard attention
    m = cfg.mla
    cq = jax.nn.standardize  # noqa: F841 (unused; clarity)
    k_nope = jnp.einsum("btr,rhn->bthn", ckv, p["wk_b"])
    v_full = jnp.einsum("btr,rhv->bthv", ckv, p["wv_b"])
    # recompute q_nope explicitly
    from repro.models.layers import linear, rmsnorm, rope as rope_fn
    cqv = rmsnorm(p["q_ln"], linear(p["wq_a"], h), cfg.norm_eps)
    q = linear(p["wq_b"], cqv).reshape(b, t, cfg.n_heads,
                                       m.qk_nope_dim + m.qk_rope_dim)
    q_nope = q[..., :m.qk_nope_dim]
    kr_b = jnp.broadcast_to(kr[:, :, None, :],
                            (b, t, cfg.n_heads, m.qk_rope_dim))
    q_full = jnp.concatenate([q_nope, rope_fn(q[..., m.qk_nope_dim:], pos,
                                              cfg.rope_theta)], -1)
    k_full = jnp.concatenate([k_nope, kr_b], -1)
    from repro.core.attention import dense_attention
    want = dense_attention(q_full, k_full, v_full, mask, scale=blk.scale)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.reshape(b, t, -1)),
                               atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# serving params: created in the compute dtype
# ---------------------------------------------------------------------------

def test_init_in_compute_dtype_equals_cast_fp32_init():
    from repro.models.model import build_model
    model = build_model(get_config("qwen3-4b").smoke())
    f32 = model.init(KEY)
    bf16 = model.init(KEY, dtype=jnp.bfloat16)
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(f32))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(bf16))
    # one jitted program may round the fp32 init differently in the last
    # bit before the cast: allow one bf16 ulp
    for a, b in zip(jax.tree.leaves(f32), jax.tree.leaves(bf16)):
        np.testing.assert_allclose(np.asarray(b, np.float32), np.asarray(a),
                                   rtol=2 ** -7, atol=0)
