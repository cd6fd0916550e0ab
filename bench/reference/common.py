"""What every plain reference shares: sizes, precision, QUOKA selection.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no paged cache, no batching.  It imports nothing of the program.
A reference module (``bench/reference/<model_type>.py``) runs one request
at a time, the whole sequence (prompt, then every served token but the
last) in one pass, and returns the logits at the positions that produced
the served tokens.  Its layer is its own; the attention it calls is this
module's ``attend``:

    att = softmax(q k^T * scale) v   over the keys each query may see
                                     (below); scale 1/sqrt(head_dim)
                                     unless the spec sets ``attn_scale``

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
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

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
    tied: bool          # read-out through emb^T, else through lm_head
    low: bool = False
    q_block: int = 512
    attn_scale: Optional[float] = None    # softmax scale; 1/sqrt(head_dim)


def spec_for(config: dict, t_max: int, n_max: int, capacity: int,
             low: bool = False) -> RefSpec:
    """The reference's sizes for a configuration file and a geometry, from
    the keys every dense GQA configuration publishes."""
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
        t_max=t_max, n_max=int(n_max),
        tied=bool(config["tie_word_embeddings"]), low=low,
        q_block=min(512, t_max))


def sequence(prompt, served, s: RefSpec):
    """``(tokens, n_prompt, length)`` of one request for a jitted forward:
    the prompt, then each served token but the last, padded to ``t_max``."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    n = len(served)
    if len(seq) > s.t_max or n > s.n_max:
        raise ValueError(f"request of {len(prompt)} + {n} tokens exceeds the "
                         f"reference's {s.t_max} positions / {s.n_max} served")
    tokens = np.zeros((s.t_max,), np.int32)
    tokens[:len(seq)] = seq
    return jnp.asarray(tokens), jnp.int32(len(prompt)), jnp.int32(len(seq))


def _fp8(x):
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, b, low):
    """``a @ b`` at HIGHEST; with ``low`` on float8-rounded operands."""
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def es(eq, a, b, low):
    """``einsum(eq, a, b)`` at HIGHEST; with ``low`` on float8-rounded
    operands."""
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """Rotary embedding, the two halves of each head rotated together."""
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
    sc = es("unkd,tkd->uknt", qbar, kn, s.low).max(2)          # (u, kv, t)
    sc = jnp.where(prior[:, None, :], sc, NEG)
    sink = prior & (t[None, :] < s.keep_first)
    sc = jnp.where(sink[:, None, :], jnp.inf, sc)
    thr = jax.lax.top_k(sc, min(s.budget, s.t_max))[0][..., -1:]
    chosen = every & (sc >= thr)
    up = s.t_max // s.chunk
    on = jnp.concatenate([jnp.full((up,), s.select_prefill),
                          jnp.full((s.n_max - 1,), s.select_decode)])
    return jnp.where(on[:, None, None], chosen, every)


def attend(q, k, v, n_prompt, length, s: RefSpec):
    """(t_max, n_heads, head_dim) attention of rotated ``q`` (t_max,
    n_heads, head_dim) over ``k``, ``v`` (t_max, n_kv, head_dim), each
    query over the keys it may see, in blocks of ``q_block`` queries."""
    t_max, c = s.t_max, s.chunk
    up = t_max // c
    g = s.n_heads // s.n_kv
    t = jnp.arange(t_max)
    unit = jnp.where(t < n_prompt, t // c, up + (t - n_prompt))
    starts = jnp.concatenate([jnp.arange(up) * c,
                              n_prompt + jnp.arange(s.n_max - 1)])
    sel = _selection(q, k, n_prompt, starts, s)
    n_units = starts.shape[0]
    scale = s.head_dim ** -0.5 if s.attn_scale is None else s.attn_scale
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
        lg = es("rkgd,tkd->kgrt", qb, k, s.low) * scale
        lg = jnp.where(allowed.transpose(1, 0, 2)[:, None], lg, NEG)
        p = jax.nn.softmax(lg, axis=-1)
        o = es("kgrt,tkd->rkgd", p, v, s.low)
        out.append(o.reshape(-1, s.n_heads, s.head_dim))
    return jnp.concatenate(out, 0)
