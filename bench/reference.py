"""Plain reference of a served dense GQA configuration with QUOKA selection.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no paged cache, no batching.  It imports nothing of the program.
One request at a time, the whole sequence (prompt, then every served token
but the last) in one pass, and returns the logits at the positions that
produced the served tokens.

What it computes, layer by layer (the program's architecture, departures
from the published models listed in ``bench/configs/*.json``):

    x   = emb[tokens]
    h   = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv ; rotary on q and k
    att = softmax(q k^T / sqrt(head_dim)) v   over the keys each query may
          see (below)
    x  += att Wo ; x += W_down(silu(W_gate rmsnorm(x)) * W_up rmsnorm(x))
    logits = rmsnorm(x) emb^T                    (tied read-out)

Which keys a query sees.  The prompt is served in chunks of ``chunk``
tokens from position 0, and every served token after the first is decoded
alone; each chunk or decoded token is a *unit* starting at ``s``.  A query
sees the keys of its own unit up to itself, and of the prior context
(positions < s): all of it where the unit does not select, else the
``budget`` keys with the highest QUOKA score, per KV head:

  * the unit's queries (invalid tail rows replaced by its first query) are
    ranked by how far their cosine to the unit's mean query is below 1,
    averaged over each KV group's heads, and the ``n_queries`` farthest
    are kept (all of them when the unit has no more than ``n_queries``);
  * a key's score is the max over kept queries of the group-mean of the
    normalised queries against the normalised key;
  * the first ``keep_first`` positions are always kept.

A unit selects when the program's cache view (``capacity`` tokens) holds
more than ``budget + unit width`` keys, the rule by which the program
takes its dense branch.

``low=True`` is the control: every matrix product takes its operands
rounded to float8 e4m3 with one scale per operand, the precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
F8_MAX = 448.0


@dataclass(frozen=True)
class RefSpec:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int
    eps: float
    theta: float
    chunk: int
    budget: int
    n_queries: int
    keep_first: int
    select_prefill: bool
    select_decode: bool
    t_max: int          # padded sequence length (a multiple of chunk)
    n_max: int          # most served tokens of one request
    low: bool = False
    q_block: int = 512


def spec_for(config: dict, t_max: int, n_max: int, capacity: int,
             low: bool = False) -> RefSpec:
    """The reference's sizes for a configuration file and a geometry."""
    prog = config["program"]
    chunk = int(prog["chunk_size"])
    budget = min(int(prog["budget"]), capacity)
    quoka = prog["method"] == "quoka"
    t_max = -(-t_max // chunk) * chunk
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return RefSpec(
        d_model=d, n_heads=h, n_kv=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or d // h),
        vocab=int(config["vocab_size"]), eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), chunk=chunk, budget=budget,
        n_queries=int(prog["n_queries"]),
        keep_first=int(prog["keep_first"]),
        select_prefill=quoka and capacity > budget + chunk,
        select_decode=quoka and capacity > budget + 1,
        t_max=t_max, n_max=int(n_max), low=low,
        q_block=min(512, t_max))


def _fp8(x):
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _es(eq, a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _l2n(x):
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-8)


def _prefill_qbar(q, n_prompt, s: RefSpec):
    """(units, kept queries, n_kv, head_dim) group-mean normalised queries
    of every prompt chunk."""
    t, c, g = s.t_max, s.chunk, s.n_heads // s.n_kv
    up = t // c
    qf = q.reshape(up, c, s.n_heads, s.head_dim)
    qv = (jnp.arange(t) < n_prompt).reshape(up, c)
    first = jnp.argmax(qv, axis=1)
    q0 = jnp.take_along_axis(qf, first[:, None, None, None], axis=1)
    qs = jnp.where(qv[..., None, None], qf, q0)
    qn = _l2n(qs).reshape(up, c, s.n_kv, g, s.head_dim)
    if c <= s.n_queries:
        return qn.mean(3)
    w = qv[..., None, None].astype(jnp.float32)
    mq = (qs * w).sum(1, keepdims=True) / jnp.maximum(w.sum(1, keepdims=True),
                                                      1.0)
    cos = (qs * mq).sum(-1) / (jnp.linalg.norm(qs, axis=-1)
                               * jnp.linalg.norm(mq, axis=-1) + 1e-8)
    far = jnp.where(qv[..., None], -cos, -jnp.inf)              # (up, c, h)
    far = far.reshape(up, c, s.n_kv, g).mean(-1)                # (up, c, kv)
    _, idx = jax.lax.top_k(far.transpose(0, 2, 1), s.n_queries)  # (up,kv,nq)
    kept = jnp.take_along_axis(qn.transpose(0, 2, 1, 3, 4),
                               idx[..., None, None], axis=2)    # (up,kv,nq,g,d)
    return kept.mean(3).transpose(0, 2, 1, 3)


def _decode_qbar(q, n_prompt, s: RefSpec, n_kept: int):
    nd = s.n_max - 1
    g = s.n_heads // s.n_kv
    rows = jnp.clip(n_prompt + jnp.arange(nd), 0, s.t_max - 1)
    qn = _l2n(q[rows]).reshape(nd, s.n_kv, g, s.head_dim).mean(2)
    return jnp.broadcast_to(qn[:, None], (nd, n_kept, s.n_kv, s.head_dim))


def _selection(q, k, n_prompt, starts, s: RefSpec):
    """(units, n_kv, t_max) bool: the prior keys each unit attends."""
    t = jnp.arange(s.t_max)
    prior = t[None, :] < starts[:, None]                        # (u, t)
    every = jnp.broadcast_to(prior[:, None, :],
                             (starts.shape[0], s.n_kv, s.t_max))
    if not (s.select_prefill or s.select_decode):
        return every
    qp = _prefill_qbar(q, n_prompt, s)
    qd = _decode_qbar(q, n_prompt, s, qp.shape[1])
    qbar = jnp.concatenate([qp, qd], 0)
    kn = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-16)
    sc = _es("unkd,tkd->uknt", qbar, kn, s.low).max(2)         # (u, kv, t)
    sc = jnp.where(prior[:, None, :], sc, NEG)
    sink = prior & (t[None, :] < s.keep_first)
    sc = jnp.where(sink[:, None, :], jnp.inf, sc)
    thr = jax.lax.top_k(sc, min(s.budget, s.t_max))[0][..., -1:]
    chosen = every & (sc >= thr)
    up = s.t_max // s.chunk
    on = jnp.concatenate([jnp.full((up,), s.select_prefill),
                          jnp.full((s.n_max - 1,), s.select_decode)])
    return jnp.where(on[:, None, None], chosen, every)


def _attend(q, k, v, n_prompt, length, s: RefSpec):
    t_max, c = s.t_max, s.chunk
    up = t_max // c
    g = s.n_heads // s.n_kv
    t = jnp.arange(t_max)
    unit = jnp.where(t < n_prompt, t // c, up + (t - n_prompt))
    starts = jnp.concatenate([jnp.arange(up) * c,
                              n_prompt + jnp.arange(s.n_max - 1)])
    sel = _selection(q, k, n_prompt, starts, s)
    n_units = starts.shape[0]
    scale = s.head_dim ** -0.5
    out = []
    for b0 in range(0, t_max, s.q_block):
        rows = t[b0:b0 + s.q_block]
        u = unit[b0:b0 + s.q_block]
        allowed = sel[jnp.clip(u, 0, n_units - 1)]              # (r, kv, t)
        same = ((unit[None, :] == u[:, None]) & (t[None, :] <= rows[:, None])
                & (t[None, :] < length))
        allowed = allowed | same[:, None, :]
        pad = rows >= length
        allowed = jnp.where(pad[:, None, None],
                            (t[None, :] == rows[:, None])[:, None, :],
                            allowed)
        qb = q[b0:b0 + s.q_block].reshape(-1, s.n_kv, g, s.head_dim)
        lg = _es("rkgd,tkd->kgrt", qb, k, s.low) * scale
        lg = jnp.where(allowed.transpose(1, 0, 2)[:, None], lg, NEG)
        p = jax.nn.softmax(lg, axis=-1)
        o = _es("kgrt,tkd->rkgd", p, v, s.low)
        out.append(o.reshape(-1, s.n_heads, s.head_dim))
    return jnp.concatenate(out, 0)


def _layer(x, p, n_prompt, length, s: RefSpec):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t = s.t_max
    pos = jnp.arange(t)
    h = _rms(x, p["ln1"]["g"], s.eps)
    q = _mm(h, p["wq"]["w"], s.low).reshape(t, s.n_heads, s.head_dim)
    k = _mm(h, p["wk"]["w"], s.low).reshape(t, s.n_kv, s.head_dim)
    v = _mm(h, p["wv"]["w"], s.low).reshape(t, s.n_kv, s.head_dim)
    q, k = _rope(q, pos, s.theta), _rope(k, pos, s.theta)
    att = _attend(q, k, v, n_prompt, length, s)
    x = x + _mm(att.reshape(t, -1), p["wo"]["w"], s.low)
    h = _rms(x, p["ln2"]["g"], s.eps)
    m = p["mlp"]
    up = jax.nn.silu(_mm(h, m["gate"]["w"], s.low)) * _mm(h, m["up"]["w"],
                                                          s.low)
    return x + _mm(up, m["down"]["w"], s.low)


@partial(jax.jit, static_argnums=(4,))
def _forward(params, tokens, n_prompt, length, s: RefSpec):
    emb = params["embed"]["emb"]
    x = emb[tokens].astype(jnp.float32)
    (layers,) = params["stacks"][0]

    def body(x, p):
        return _layer(x, p, n_prompt, length, s), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x, params["ln_f"]["g"].astype(jnp.float32), s.eps)
    rows = jnp.clip(n_prompt - 1 + jnp.arange(s.n_max), 0, s.t_max - 1)
    return _mm(x[rows], emb.astype(jnp.float32).T, s.low)


def logits(params, prompt, served, s: RefSpec):
    """(len(served), vocab) float32 logits at the positions that produced
    each served token: the last prompt position, then each served token
    but the last."""
    import numpy as np
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    n = len(served)
    if len(seq) > s.t_max or n > s.n_max:
        raise ValueError(f"request of {len(prompt)} + {n} tokens exceeds the "
                         f"reference's {s.t_max} positions / {s.n_max} served")
    tokens = np.zeros((s.t_max,), np.int32)
    tokens[:len(seq)] = seq
    out = _forward(params, jnp.asarray(tokens), jnp.int32(len(prompt)),
                   jnp.int32(len(seq)), s)
    return out[:n]
