"""Primitive NN layers (no flax on this host — explicit param pytrees).

Conventions:
  * every layer is a pair of pure functions ``<name>_init(key, ...) -> params``
    and ``<name>(params, x, ...) -> y``;
  * params are nested dicts of jnp arrays; leaves are created in fp32
    (``Model.init(key, dtype)`` casts them for serving) and cast to the
    compute dtype at apply time by the caller's policy.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------------------
# initialisers
# ----------------------------------------------------------------------------

def _normal(key, shape, std, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def linear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                std: Optional[float] = None, dtype=jnp.float32):
    std = (1.0 / math.sqrt(d_in)) if std is None else std
    p = {"w": _normal(key, (d_in, d_out), std, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"emb": _normal(key, (vocab, d), 0.02, dtype)}


def embed(p, tokens, dtype):
    return p["emb"].astype(dtype)[tokens]


def unembed(p, x):
    """Tied read-out: logits = x @ emb^T (fp32 for a stable softmax/xent)."""
    return x.astype(jnp.float32) @ p["emb"].astype(jnp.float32).T


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def rmsnorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["g"]).astype(dt)


def layernorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]).astype(dt)


def groupnorm(x, n_groups: int, eps: float = 1e-5):
    """Per-head group norm used by RWKV6 (no learned affine here)."""
    dt = x.dtype
    shp = x.shape
    xf = x.astype(jnp.float32).reshape(shp[:-1] + (n_groups, shp[-1] // n_groups))
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(shp).astype(dt)


# ----------------------------------------------------------------------------
# activations / MLPs
# ----------------------------------------------------------------------------

def _act(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


def mlp_init(key, d: int, d_ff: int, *, gated: bool = True, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"up": linear_init(ks[0], d, d_ff, dtype=dtype),
         "down": linear_init(ks[1], d_ff, d, dtype=dtype)}
    if gated:
        p["gate"] = linear_init(ks[2], d, d_ff, dtype=dtype)
    return p


def mlp(p, x, act: str = "silu"):
    up = linear(p["up"], x)
    if "gate" in p:
        h = _act(act, linear(p["gate"], x)) * up
    else:
        h = _act(act, up)
    return linear(p["down"], h)


# ----------------------------------------------------------------------------
# positions
# ----------------------------------------------------------------------------

def rope(x, positions, theta: float):
    """Rotary embedding.  x: (..., T, n_heads, head_dim); positions: (..., T)."""
    dt = x.dtype
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..., T, half)
    sin = jnp.sin(ang)[..., None, :]                                 # (..., T, 1, half)
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(dt)


def sinusoidal(positions, d: int, dtype=jnp.float32):
    """Whisper-style sinusoidal position embedding.  positions: (..., T)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ----------------------------------------------------------------------------
# misc
# ----------------------------------------------------------------------------

def cosine_sim(a, b, axis: int = -1, eps: float = 1e-8):
    """CosSim along `axis` with broadcasting; computed in fp32."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    num = jnp.sum(af * bf, axis=axis)
    den = jnp.linalg.norm(af, axis=axis) * jnp.linalg.norm(bf, axis=axis)
    return num / (den + eps)


def l2_normalize(x, axis: int = -1, eps: float = 1e-8):
    xf = x.astype(jnp.float32)
    return (xf / (jnp.linalg.norm(xf, axis=axis, keepdims=True) + eps)).astype(x.dtype)
