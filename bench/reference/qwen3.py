"""Plain reference of ``model_type`` ``qwen3``: a dense GQA decoder.

Layer by layer (departures from the published model are listed in the
configuration files, ``bench/configs/*.json``):

    x   = emb[tokens]
    h   = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv ; rotary on q and k
    att = common.attend(q, k, v)   (QUOKA selection, 1/sqrt(head_dim))
    x  += att Wo ; x += W_down(silu(W_gate rmsnorm(x)) * W_up rmsnorm(x))
    logits = rmsnorm(x) emb^T              (tie_word_embeddings: true)
    logits = rmsnorm(x) W_lm_head          (tie_word_embeddings: false)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference import common
from reference.common import RefSpec, attend, mm, rms, rope

# the sizes are the keys every dense GQA configuration publishes
spec_for = common.spec_for


def _layer(x, p, n_prompt, length, s: RefSpec):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t = s.t_max
    pos = jnp.arange(t)
    h = rms(x, p["ln1"]["g"], s.eps)
    q = mm(h, p["wq"]["w"], s.low).reshape(t, s.n_heads, s.head_dim)
    k = mm(h, p["wk"]["w"], s.low).reshape(t, s.n_kv, s.head_dim)
    v = mm(h, p["wv"]["w"], s.low).reshape(t, s.n_kv, s.head_dim)
    q, k = rope(q, pos, s.theta), rope(k, pos, s.theta)
    att = attend(q, k, v, n_prompt, length, s)
    x = x + mm(att.reshape(t, -1), p["wo"]["w"], s.low)
    h = rms(x, p["ln2"]["g"], s.eps)
    m = p["mlp"]
    up = jax.nn.silu(mm(h, m["gate"]["w"], s.low)) * mm(h, m["up"]["w"],
                                                        s.low)
    return x + mm(up, m["down"]["w"], s.low)


@partial(jax.jit, static_argnums=(4,))
def _forward(params, tokens, n_prompt, length, s: RefSpec):
    emb = params["embed"]["emb"]
    x = emb[tokens].astype(jnp.float32)
    (layers,) = params["stacks"][0]

    def body(x, p):
        return _layer(x, p, n_prompt, length, s), None

    x, _ = jax.lax.scan(body, x, layers)
    x = rms(x, params["ln_f"]["g"].astype(jnp.float32), s.eps)
    rows = jnp.clip(n_prompt - 1 + jnp.arange(s.n_max), 0, s.t_max - 1)
    head = emb.astype(jnp.float32).T if s.tied \
        else params["lm_head"]["w"].astype(jnp.float32)
    return mm(x[rows], head, s.low)


def logits(params, prompt, served, s: RefSpec):
    """(len(served), vocab) float32 logits at the positions that produced
    each served token: the last prompt position, then each served token
    but the last."""
    return _forward(params, *common.sequence(prompt, served, s), s)[
        :len(served)]
