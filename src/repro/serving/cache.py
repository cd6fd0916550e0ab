"""Per-block runtime caches.

All caches are NamedTuples (pytree-friendly, scan-stackable).  KV slots carry
their absolute position (``pos``, -1 = empty); masks everywhere derive from
positions, so full caches, sliding-window ring buffers and QUOKA-selected
subsets share one mask code path (see core/attention.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class KVCache(NamedTuple):
    k: jax.Array      # (b, cap, n_kv, hd)
    v: jax.Array      # (b, cap, n_kv, hd)
    pos: jax.Array    # (b, cap) int32, -1 = empty

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


def kv_init(batch: int, cap: int, n_kv: int, hd: int, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, cap, n_kv, hd), dtype),
        v=jnp.zeros((batch, cap, n_kv, hd), dtype),
        pos=jnp.full((batch, cap), -1, jnp.int32),
    )


def _pos_rows(start, t: int, b: int) -> jax.Array:
    """Default stored positions for a chunk write: start + [0, t)."""
    s = jnp.asarray(start, jnp.int32)
    offs = jnp.arange(t, dtype=jnp.int32)
    if s.ndim == 0:
        return (s + offs)[None, :].repeat(b, 0)
    return s[:, None] + offs[None, :]


def kv_write(cache: KVCache, k_new, v_new, start, pos_new=None) -> KVCache:
    """Append a contiguous chunk at slot `start` (slot == absolute position
    for linear caches).  `start` may be a traced scalar or a per-row ``(b,)``
    vector (continuous batching: every request in the step batch writes at
    its own offset).  ``pos_new`` optionally overrides the stored positions
    with an explicit ``(b, t)`` array — pad slots marked ``-1`` there are
    invalid and mask themselves out of attention and selection scoring."""
    with jax.named_scope("kv_write"):
        b, t = k_new.shape[:2]
        s = jnp.asarray(start, jnp.int32)
        pos_new = _pos_rows(s, t, b) if pos_new is None \
            else jnp.asarray(pos_new, jnp.int32)
        if s.ndim == 0:
            z = jnp.zeros((), jnp.int32)
            return KVCache(
                k=jax.lax.dynamic_update_slice(cache.k,
                                               k_new.astype(cache.k.dtype),
                                               (z, s, z, z)),
                v=jax.lax.dynamic_update_slice(cache.v,
                                               v_new.astype(cache.v.dtype),
                                               (z, s, z, z)),
                pos=jax.lax.dynamic_update_slice(cache.pos, pos_new, (z, s)),
            )

        def row(kb, vb, pb, kn, vn, pn, si):
            z = jnp.zeros((), jnp.int32)
            return (jax.lax.dynamic_update_slice(kb, kn.astype(kb.dtype),
                                                 (si, z, z)),
                    jax.lax.dynamic_update_slice(vb, vn.astype(vb.dtype),
                                                 (si, z, z)),
                    jax.lax.dynamic_update_slice(pb, pn, (si,)))

        k2, v2, p2 = jax.vmap(row)(cache.k, cache.v, cache.pos,
                                   k_new, v_new, pos_new, s)
        return KVCache(k=k2, v=v2, pos=p2)


def kv_write_ring(cache: KVCache, k_new, v_new, start, pos_new=None) -> KVCache:
    """Append modulo capacity (sliding-window ring buffer).  The chunk may
    wrap; a scatter over per-token slots handles it with static shapes.
    ``start`` must be a (possibly traced) scalar — windowed layers are not
    part of the paged/continuous path."""
    with jax.named_scope("kv_write"):
        b, t = k_new.shape[:2]
        cap = cache.capacity
        offs = jnp.arange(t, dtype=jnp.int32)
        slots = (jnp.asarray(start, jnp.int32) + offs) % cap          # (t,)
        pos_new = _pos_rows(start, t, b) if pos_new is None \
            else jnp.asarray(pos_new, jnp.int32)
        return KVCache(
            k=cache.k.at[:, slots].set(k_new.astype(cache.k.dtype)),
            v=cache.v.at[:, slots].set(v_new.astype(cache.v.dtype)),
            pos=cache.pos.at[:, slots].set(pos_new),
        )


class LatentCache(NamedTuple):
    """DeepSeek MLA compressed cache: per-token latent + shared rope key."""
    ckv: jax.Array    # (b, cap, kv_lora_rank)
    krope: jax.Array  # (b, cap, qk_rope_dim)
    pos: jax.Array    # (b, cap)

    @property
    def capacity(self) -> int:
        return self.ckv.shape[1]


def latent_init(batch: int, cap: int, r: int, rope: int, dtype) -> LatentCache:
    return LatentCache(
        ckv=jnp.zeros((batch, cap, r), dtype),
        krope=jnp.zeros((batch, cap, rope), dtype),
        pos=jnp.full((batch, cap), -1, jnp.int32),
    )


def latent_write(cache: LatentCache, ckv_new, krope_new, start,
                 pos_new=None) -> LatentCache:
    """MLA twin of ``kv_write``: same scalar-or-per-row ``start`` and
    optional explicit ``pos_new`` semantics."""
    with jax.named_scope("kv_write"):
        b, t = ckv_new.shape[:2]
        s = jnp.asarray(start, jnp.int32)
        pos_new = _pos_rows(s, t, b) if pos_new is None \
            else jnp.asarray(pos_new, jnp.int32)
        if s.ndim == 0:
            z = jnp.zeros((), jnp.int32)
            return LatentCache(
                ckv=jax.lax.dynamic_update_slice(
                    cache.ckv, ckv_new.astype(cache.ckv.dtype), (z, s, z)),
                krope=jax.lax.dynamic_update_slice(
                    cache.krope, krope_new.astype(cache.krope.dtype),
                    (z, s, z)),
                pos=jax.lax.dynamic_update_slice(cache.pos, pos_new, (z, s)),
            )

        def row(cb, rb, pb, cn, rn, pn, si):
            z = jnp.zeros((), jnp.int32)
            return (jax.lax.dynamic_update_slice(cb, cn.astype(cb.dtype),
                                                 (si, z)),
                    jax.lax.dynamic_update_slice(rb, rn.astype(rb.dtype),
                                                 (si, z)),
                    jax.lax.dynamic_update_slice(pb, pn, (si,)))

        c2, r2, p2 = jax.vmap(row)(cache.ckv, cache.krope, cache.pos,
                                   ckv_new, krope_new, pos_new, s)
        return LatentCache(ckv=c2, krope=r2, pos=p2)


class MambaCache(NamedTuple):
    conv: jax.Array   # (b, d_conv - 1, conv_channels) trailing inputs
    ssd: jax.Array    # (b, n_heads, head_dim, d_state) fp32 state


class RWKVCache(NamedTuple):
    shift_tm: jax.Array  # (b, d) last token entering time-mix
    shift_cm: jax.Array  # (b, d) last token entering channel-mix
    wkv: jax.Array       # (b, n_heads, head_dim, head_dim) fp32 state


class CrossKV(NamedTuple):
    """Encoder-derived cross-attention KV (whisper); computed once."""
    k: jax.Array      # (b, n_ctx, n_kv, hd)
    v: jax.Array


class BlockCache(NamedTuple):
    """Union cache for one block; unused fields are () placeholders so the
    pytree structure stays uniform inside a scanned stack."""
    kv: object = ()
    latent: object = ()
    mamba: object = ()
    rwkv: object = ()
    cross: object = ()
