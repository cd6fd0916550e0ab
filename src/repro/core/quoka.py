"""QUOKA scoring primitives (paper Algorithm 1, stages 1-3).

Three stages, all standard linear algebra (the paper's portability claim):

  1. *Query subselection* — keep the ``N_Q`` queries most cosine-DISSIMILAR
     to the mean query of the chunk (Theorem 1: those dominate attention).
  2. *Cosine-similarity scoring* — score the kept (normalised) queries
     against normalised cached keys.
  3. *Group-aware aggregation* — **max** over the query axis (preserves
     heavy-tailed outliers, Table 10) and **mean** over GQA groups, applied
     as *pre-aggregation*: normalised queries are averaged inside each KV
     group BEFORE the ``Q̄Kᵀ`` matmul (linearity), cutting score cost by
     ``n_q/n_kv`` (paper §3.3, Table 4).

This module produces SCORES (and, on the tensor-parallel fast path,
top-k plan candidates).  The select + materialize stages live in
``core/plan.py::SelectionPlan`` — the single selection code path for every
caller (attention blocks, the standalone chunked-prefill harness, the
serving engine).

Layouts: q (b, t, n_q_heads, d); k/v caches (b, T, n_kv, d);
key positions (b, T) int32 with -1 marking empty slots.
Scores are fp32; ``NEG_INF`` marks un-selectable slots.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import QuokaConfig
from repro.core.attention import NEG_INF
from repro.models.layers import l2_normalize
from repro.sharding import ctx as shctx


class Selected(NamedTuple):
    """A gathered KV budget.  Positions are per-KV-head (b, n_kv, B).

    Validity contract: ``idx >= 0`` (``valid``) marks an attendable slot;
    -1 in ``idx`` marks padding (fewer valid KVs than the budget).  ``pos``
    is -1 at exactly the same slots, but it is a gather of its own: read it
    only where positions are needed as VALUES (sliding windows, telemetry,
    recall analysis) — callers that only mask read ``valid``, and under jit
    the unread position gather is dead code."""
    k: jax.Array          # (b, B, n_kv, d)
    v: jax.Array          # (b, B, n_kv, d)
    pos: jax.Array        # (b, n_kv, B) int32
    idx: jax.Array        # (b, n_kv, B) int32 cache slots

    @property
    def valid(self) -> jax.Array:
        """(b, n_kv, B) bool: which budget slots may be attended."""
        return self.idx >= 0


# ----------------------------------------------------------------------------
# stage 1: query subselection
# ----------------------------------------------------------------------------

def sanitize_queries(q: jax.Array, q_valid: Optional[jax.Array]) -> jax.Array:
    """Replace invalid query rows with a copy of the row's batch-first VALID
    query.

    ``q_valid`` (b, t) marks real queries; False rows are padding (ragged
    tail chunks under continuous batching, left-pad slots of ``pad_prompt``)
    whose projections come from garbage embeddings.  Overwriting them with a
    duplicate of a real query makes every later stage safe by construction:
    a duplicate can never change a max-aggregated score, and downstream
    masking (``subselect_queries``) keeps duplicates out of the mean/top-k
    whenever enough real queries exist."""
    if q_valid is None:
        return q
    first = jnp.argmax(q_valid, axis=1)                          # (b,)
    repl = jnp.take_along_axis(q, first[:, None, None, None], axis=1)
    return jnp.where(q_valid[:, :, None, None], q, repl)


def subselect_queries(q: jax.Array, n_queries: int,
                      n_kv: Optional[int] = None,
                      q_valid: Optional[jax.Array] = None) -> jax.Array:
    """Keep the ``n_queries`` queries with lowest CosSim to the mean query.

    q: (b, t, h, d)  ->  (b, n_queries, h, d).
    When t <= n_queries the input is returned unchanged (Algorithm 1 line 1).

    With ``n_kv`` given, selection is GROUP-COHERENT: the dissimilarity score
    is averaged over each GQA group and every head of a group keeps the SAME
    token indices.  This is required for the downstream pre-aggregation
    (quoka_scores averages normalised queries inside each group): with
    independent per-head top-k, slot i holds a *different token* per head and
    the group mean blends unrelated queries, washing outliers out before the
    max.  Outlier-ness is token-level in GQA models (heads of a group retrieve
    the same token — the premise of §3.3's pre-aggregation), so the group-mean
    score preserves exactly the queries pre-aggregation can represent.
    Without ``n_kv`` (or with n_kv == h) selection is per-head as before.

    ``q_valid`` (b, t) bool masks ragged-tail padding: invalid rows are
    excluded from the mean query AND ranked last by top-k, so garbage
    embeddings cannot skew the chunk statistics (callers should first run
    ``sanitize_queries`` so any invalid row that IS kept — fewer valid
    queries than ``n_queries`` — is a harmless duplicate of a real one).
    """
    b, t, h, d = q.shape
    if t <= n_queries:
        return q
    qf = q.astype(jnp.float32)
    if q_valid is not None:
        w = q_valid[:, :, None, None].astype(jnp.float32)
        cnt = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1.0)
        mq = jnp.sum(qf * w, axis=1, keepdims=True) / cnt        # (b, 1, h, d)
    else:
        mq = jnp.mean(qf, axis=1, keepdims=True)                 # (b, 1, h, d)
    num = jnp.sum(qf * mq, axis=-1)
    den = (jnp.linalg.norm(qf, axis=-1) * jnp.linalg.norm(mq, axis=-1) + 1e-8)
    s_q = -(num / den)                                           # (b, t, h)
    if q_valid is not None:
        s_q = jnp.where(q_valid[:, :, None], s_q, -jnp.inf)
    if n_kv is not None and n_kv != h:
        group = h // n_kv
        s_g = s_q.reshape(b, t, n_kv, group).mean(axis=3)        # (b, t, n_kv)
        _, top_g = jax.lax.top_k(s_g.transpose(0, 2, 1), n_queries)
        top_i = jnp.repeat(top_g, group, axis=1)                 # (b, h, N_Q)
    else:
        _, top_i = jax.lax.top_k(s_q.transpose(0, 2, 1), n_queries)
    gathered = jnp.take_along_axis(
        q.transpose(0, 2, 1, 3), top_i[..., None], axis=2)       # (b, h, N_Q, d)
    return gathered.transpose(0, 2, 1, 3)


# ----------------------------------------------------------------------------
# stages 2+3: cosine scoring with GQA pre-aggregation, max over queries
# ----------------------------------------------------------------------------

def quoka_scores(q: jax.Array, k: jax.Array, valid: jax.Array,
                 cfg: QuokaConfig) -> jax.Array:
    """Paper Algorithm 1 lines 6-10.

    q: (b, N_Q, n_q_heads, d) already sub-selected; k: (b, T, n_kv, d);
    valid: (b, T) bool (selectable prior-context slots).
    Returns fp32 scores (b, n_kv, T), NEG_INF on invalid slots.

    Backend dispatch: the default cosine+max configuration routes through
    ``kernels/ops.py::score`` (the fused Pallas scoring kernel, or its XLA
    twin below) per the resolved ``cfg.backend``.  The Table-9/10 ablation
    arms ("dot" scoring, "mean" aggregation) are outside the kernel's fixed
    semantics and always take the einsum path.
    """
    b, nq, h, d = q.shape
    n_kv = k.shape[2]
    group = h // n_kv

    if cfg.scoring == "cosine":
        qn = l2_normalize(q.astype(jnp.float32))
    elif cfg.scoring == "dot":                     # Table 9 ablation arm
        qn = q.astype(jnp.float32)
    else:
        raise ValueError(cfg.scoring)

    # pre-aggregation: mean of (normalised) queries inside each KV group
    qbar = jnp.mean(qn.reshape(b, nq, n_kv, group, d), axis=3)   # (b,N_Q,n_kv,d)

    if cfg.scoring == "cosine" and cfg.query_agg == "max":
        from repro.kernels import ops as kops
        # a concrete cfg.backend is the model's resolved choice: an exported
        # REPRO_BACKEND must not switch scoring away from it
        backend = kops.resolve_backend(cfg.backend)
        # facade path: the fused Pallas kernel (Q̄ VMEM-resident, K streamed
        # once) or its XLA twin with FUSED key normalisation (§Perf A1 —
        # scores divided by per-key norms so no normalised fp32 copy of the
        # K cache is ever materialised).  Tensor-parallel serving runs the
        # SAME facade per shard inside tp_plan_candidates' shard_map below —
        # that T-local pass is what resolved the old §Perf A7 note: when
        # n_kv < |model| the (b, n_kv, T) score tensor under-shards, and
        # constraining its T axis over `model` made XLA reshard the whole K
        # cache (measured 60 TB/chip of all-gather).  shard_map scores each
        # key where it lives and merges per-shard top-k candidates instead.
        return kops.score(qbar, k, valid, backend=backend,
                          proj=score_proj(cfg, d))
    # ablation arms ("dot" scoring / "mean" aggregation) are outside the
    # kernel's fixed semantics and keep the einsum path
    s = jnp.einsum("bnkd,btkd->bknt", qbar.astype(k.dtype), k,
                   preferred_element_type=jnp.float32)           # (b,n_kv,N_Q,T)
    if cfg.scoring == "cosine":
        # self-dot via einsum: bf16 reads, fp32 accumulation — no converted
        # copy of K is ever materialised (an astype(f32) here caused XLA to
        # hoist a full-cache f32 conversion across the prefill loop)
        sq = jnp.einsum("btkd,btkd->btk", k, k,
                        preferred_element_type=jnp.float32)
        inv = jax.lax.rsqrt(sq + 1e-16)                          # (b,T,n_kv)
        s = s * inv.transpose(0, 2, 1)[:, :, None, :]

    if cfg.query_agg == "max":                     # Table 10: max >> mean
        s_hat = jnp.max(s, axis=2)
    elif cfg.query_agg == "mean":
        s_hat = jnp.mean(s, axis=2)
    else:
        raise ValueError(cfg.query_agg)

    return jnp.where(valid[:, None, :], s_hat, NEG_INF)


def score_proj(cfg: QuokaConfig, d: int):
    """The cached low-rank scoring projection for ``cfg.score_proj_dim``,
    or None when the mode is off (or would not reduce the head dim)."""
    r = getattr(cfg, "score_proj_dim", 0)
    if not r or r >= d:
        return None
    from repro.kernels import ops as kops
    return kops.score_projection(d, r)


def prior_context_valid(key_pos: jax.Array, chunk_start) -> jax.Array:
    """Selectable slots: 0 <= pos < chunk_start (the prior context, eq. (2)).

    ``chunk_start`` may be a traced scalar (scan carry) or a per-row ``(b,)``
    vector (continuous batching: requests in one step batch sit at different
    positions).  ``key_pos`` is (b, ...) with the batch axis first: the
    cache view's (b, T) or positions already gathered from it."""
    cs = jnp.asarray(chunk_start, jnp.int32)
    if cs.ndim == 1:
        cs = cs.reshape(cs.shape + (1,) * (key_pos.ndim - 1))
    return (key_pos >= 0) & (key_pos < cs)


# ----------------------------------------------------------------------------
# tensor-parallel T-local selection (shard_map over the `model` axis)
# ----------------------------------------------------------------------------

def _tp_route(k: jax.Array, cfg: QuokaConfig):
    """Shard info when the T-local sharded selection path applies.

    The einsum/kernel path already shards well whenever the KV-head axis
    divides the `model` axis (scores shard over heads).  The failure mode —
    the old §Perf A7 note — is n_kv < |model| (granite kv=8 on 16-way TP):
    the score tensor under-shards and any attempt to constrain its T axis
    resharded the whole K cache.  In exactly that regime the cache's head
    axis is REPLICATED over `model` (sharding/specs.py drops indivisible
    axes), so each shard can score a distinct contiguous T-slice of the
    keys it already holds, locally, and only candidate (score, index)
    pairs — ``budget`` per shard — cross the interconnect."""
    if cfg.scoring != "cosine" or cfg.query_agg != "max":
        return None                        # ablation arms: einsum fallback
    info = shctx.tp_shard_info()
    if info is None:
        return None                        # no mesh policy: einsum fallback
    mesh, m_ax, _ = info
    msize = mesh.shape[m_ax]
    t, n_kv = k.shape[1], k.shape[2]
    if n_kv % msize == 0:
        return None                        # heads shard: already layout-local
    if t % msize != 0:
        return None                        # ragged key axis: fall back
    g = max(1, cfg.granularity)
    if (t // msize) % g != 0:
        return None    # selection grid straddles shard slices: fall back
    return info


def tp_plan_candidates(qs: jax.Array, k: jax.Array, key_pos: jax.Array,
                       valid: jax.Array, cfg: QuokaConfig, budget: int,
                       info) -> jax.Array:
    """T-local sharded scoring + candidate merge (old §Perf A7 note).

    Each `model` shard scores a contiguous ``T/|model|`` slice of the keys
    through the same ``kernels/ops.score`` facade as the unsharded path,
    keeps its local top candidates on the selection grid, and the shards
    merge candidates with one SMALL all-gather ((score, idx) pairs per
    shard — a few KB) instead of resharding the K cache.  The merged top-k
    is exactly ``plan.plan_from_scores``'s: descending score with ties
    broken by ascending key/block index (shard slices are contiguous and
    ascending, local top-k orders ties by index, and the merge prefers
    earlier candidate positions), so the returned PLAN INDICES — and
    therefore decoding — are bit-identical to the meshless run.

    Invalid slots score NEG_INF through the same facade and take -1 under
    the same ``> NEG_INF / 2`` test, so a plan from here is as fresh as a
    meshless one: materialize may read validity off its indices.

    Only indices leave the shard_map: the materialize stage runs outside,
    on the replicated caches (core/plan.py), so the same contiguous-gather
    lowering serves the sharded and meshless paths.  Returns the
    ``SelectionPlan.idx`` payload: (b, n_kv, budget) token slots at
    granularity 1, (b, budget//g) block ids at granularity g > 1; -1 marks
    padding.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops as kops
    from repro.sharding.specs import _axes_size

    mesh, m_ax, b_axes = info
    msize = mesh.shape[m_ax]
    b, nq, h, d = qs.shape
    t, n_kv = k.shape[1], k.shape[2]
    g = max(1, cfg.granularity)
    budget = min(budget, t)
    nb = budget // g                                      # plan slots
    tl = t // msize
    n_cand = min(nb, tl // g)                             # per-shard slots
    backend = kops.resolve_backend(cfg.backend)        # as in quoka_scores
    keep_first = cfg.keep_first
    proj = score_proj(cfg, d)

    # pre-aggregation outside the shard_map (cheap, T-independent); the
    # math matches quoka_scores' cosine branch exactly
    qn = l2_normalize(qs.astype(jnp.float32))
    qbar = jnp.mean(qn.reshape(b, nq, n_kv, h // n_kv, d), axis=3)

    b_ax = b_axes if (b_axes and b % _axes_size(mesh, b_axes) == 0) else None

    def body(qbar_l, k_l, pos_l, valid_l):
        i = jax.lax.axis_index(m_ax)
        bb = k_l.shape[0]
        ks = jax.lax.dynamic_slice_in_dim(k_l, i * tl, tl, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(valid_l, i * tl, tl, axis=1)
        ps = jax.lax.dynamic_slice_in_dim(pos_l, i * tl, tl, axis=1)
        s = kops.score(qbar_l, ks, vs, backend=backend,
                       proj=proj)                         # (b, n_kv, tl)
        if keep_first:
            sink = (ps >= 0) & (ps < keep_first)          # plan's sink rule
            s = jnp.where(sink[:, None, :] & (s > NEG_INF / 2), jnp.inf, s)
        if g == 1:
            cs, ci = jax.lax.top_k(s, n_cand)             # local candidates
            ci = ci + i * tl                              # -> global indices
            cs = jax.lax.all_gather(cs, m_ax, axis=2, tiled=True)
            ci = jax.lax.all_gather(ci, m_ax, axis=2, tiled=True)
            top_s, cpos = jax.lax.top_k(cs, budget)       # merge (replicated)
            top_i = jnp.take_along_axis(ci, cpos, axis=2)  # (b, n_kv, B)
            good = top_s > NEG_INF / 2
            return jnp.where(good, top_i, -1)
        # block-granular: pool token scores to the local block grid first —
        # max is associative, so local-max-then-merge equals the meshless
        # reshape-max over the full key axis, element for element
        sb = s.reshape(bb, n_kv, tl // g, g).max(axis=3).max(axis=1)
        cs, ci = jax.lax.top_k(sb, n_cand)                # (b, n_cand)
        ci = ci + i * (tl // g)                           # -> global block ids
        cs = jax.lax.all_gather(cs, m_ax, axis=1, tiled=True)
        ci = jax.lax.all_gather(ci, m_ax, axis=1, tiled=True)
        top_s, cpos = jax.lax.top_k(cs, nb)
        top_i = jnp.take_along_axis(ci, cpos, axis=1)     # (b, NB)
        good = top_s > NEG_INF / 2
        return jnp.where(good, top_i, -1)

    out_spec = P(b_ax, None, None) if g == 1 else P(b_ax, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_ax, None, None, None), P(b_ax, None, None, None),
                  P(b_ax, None), P(b_ax, None)),
        out_specs=out_spec,
        check_vma=False)(qbar, k, key_pos, valid)
