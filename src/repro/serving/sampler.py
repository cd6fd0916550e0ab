"""Token sampling: greedy / temperature / top-k / top-p."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 0.0        # 0 = greedy
    top_k: Optional[int] = None
    top_p: Optional[float] = None


def sample(logits, key, cfg: SamplerConfig):
    """logits: (b, V) fp32 -> (b,) int32."""
    with jax.named_scope("sample"):
        return _sample(logits, key, cfg)


def _sample(logits, key, cfg: SamplerConfig):
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits / cfg.temperature
    if cfg.top_k is not None:
        kth = jax.lax.top_k(lg, cfg.top_k)[0][:, -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if cfg.top_p is not None:
        srt = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # index of the token whose cumulative mass crosses p (kept).  Two
        # degenerate edges: csum[0] >= p gives cutoff 0 — the nucleus is
        # "empty" but the max-prob token must always survive — and float
        # rounding can leave csum[-1] < p, pushing the count to V; clamp it.
        cutoff_idx = jnp.minimum(jnp.sum(csum < cfg.top_p, axis=-1,
                                         keepdims=True), lg.shape[-1] - 1)
        kth = jnp.take_along_axis(srt, cutoff_idx, axis=-1)
        keep = (lg >= kth) | (lg >= jnp.max(lg, axis=-1, keepdims=True))
        lg = jnp.where(keep, lg, -jnp.inf)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
