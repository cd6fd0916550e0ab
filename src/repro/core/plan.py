"""SelectionPlan — the single KV-selection code path.

Selection used to be smeared across three call sites (``quoka_select``,
``selection.select`` and the per-block gather logic); this module replaces
all of them with one explicit three-stage pipeline:

    scores = plan_scores(method, q, k, key_pos, chunk_start, cfg)   # stage 1
    plan   = plan_from_scores(scores, key_pos, cfg, budget)         # stage 2
    sel    = materialize(plan, k, v, key_pos, chunk_start, cfg)     # stage 3

``build`` fuses stages 1+2 (including the tensor-parallel T-local fast
path, which produces plan indices directly); ``select`` fuses all three.

A plan is *just indices* — cheap to carry, compare and reuse:

  * granularity 1 (default): ``idx`` is (b, n_kv, B) per-head token slots,
    exactly the paper's Algorithm 1 top-k (bit-identical to the legacy
    token path, including sink protection and tie order).
  * granularity g > 1: ``idx`` is (b, B//g) BLOCK ids on the fixed g-token
    selection grid, shared across KV heads (CompactAttention-style).  A
    block's score is the max of its token scores over all heads, so the
    union of per-head winners is covered; blocks straddling the chunk
    boundary are selected whole and their not-yet-prior tokens re-masked at
    materialize time (the "block-union across chunk boundaries" rule).
    Setting g to the paged pool's block size makes a plan a *block-table
    sub-view*: materialize gathers whole (g, n_kv, d) slabs — XLA lowers it
    to contiguous block slices (slice size g on the token axis), never a
    per-token gather (asserted by tests/test_selection_plan.py on the HLO).

Cross-layer reuse (``QuokaConfig.reuse_interval`` / ``correction_layers``)
threads a ``PlanCarry`` through the layer scan (models/stack.py): layer L
builds, layers L+1..L+s-1 reuse, correction layers force a rebuild.
``refresh`` is the per-layer decision point.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import QuokaConfig
from repro.core import quoka as qk
from repro.core import selection as sel_scores
from repro.core.attention import NEG_INF
from repro.core.quoka import Selected, prior_context_valid
from repro.kernels import ops as kops
from repro.sharding import ctx as shctx


class SelectionPlan(NamedTuple):
    """Block/token-granular top-k indices over a KV cache view.

    idx: int32, -1 marks padding (fewer selectable slots than the budget).
      granularity == 1 -> (b, n_kv, B) token slots per KV head;
      granularity  > 1 -> (b, B//g) grid block ids shared across heads.
    """
    idx: jax.Array


class PlanCarry(NamedTuple):
    """Scan-carried plan state for cross-layer reuse: the last built plan's
    indices plus a traced validity flag (False until the first build)."""
    idx: jax.Array
    valid: jax.Array         # () bool


class LayerObs(NamedTuple):
    """Per-layer in-jit selection telemetry — four device SCALARS (f32),
    cheap enough to ride out of ``jax.jit`` as extra outputs (the aux-stats
    pytree contract; no host callbacks on the hot path).

    NaN means "not applicable": non-attention layers are all-NaN; dense
    layers have no budget.

    sel_tokens    selected valid KV tokens, mean over batch & KV heads
    ctx_tokens    selectable prior-context tokens, mean over batch
                  (sel_tokens / ctx_tokens is the live selected-KV fraction
                  — the paper's "88% fewer key-value pairs" axis)
    budget_tokens the resolved grid-aligned B_SA (static, as f32)
    refreshed     1.0 if this layer BUILT a plan, 0.0 if it reused one
    """
    sel_tokens: jax.Array
    ctx_tokens: jax.Array
    budget_tokens: jax.Array
    refreshed: jax.Array


# ----------------------------------------------------------------------------
# grid helpers — the ONE place budgets meet the selection grid
# ----------------------------------------------------------------------------

def grid(cfg: QuokaConfig) -> int:
    """Static selection granularity in tokens (>= 1)."""
    return max(1, int(getattr(cfg, "granularity", 1)))


# the one grid-flooring implementation lives next to resolve_budget
floor_to_grid = sel_scores.floor_to_grid


def resolve_budget(cfg: QuokaConfig, context_len: int) -> int:
    """Effective grid-aligned B_SA for a context length, clamped to the
    view — the single budget-resolution entry point for plan callers
    (selection.resolve_budget already grid-floors; callers must not
    re-round)."""
    return floor_to_grid(min(sel_scores.resolve_budget(cfg, context_len),
                             context_len), grid(cfg))


def plan_idx_shape(cfg: QuokaConfig, b: int, n_kv: int, t: int,
                   budget: Optional[int] = None):
    """Static shape of ``SelectionPlan.idx`` for a (b, T, n_kv, d) cache —
    what a scan carry must be allocated as (see models/stack.py)."""
    g = grid(cfg)
    bud = floor_to_grid(min(budget or sel_scores.resolve_budget(cfg, t), t),
                        g)
    return (b, n_kv, bud) if g == 1 else (b, bud // g)


# ----------------------------------------------------------------------------
# stage 1: score
# ----------------------------------------------------------------------------

def plan_scores(method: str, q, k, key_pos, chunk_start, cfg: QuokaConfig,
                q_valid: Optional[jax.Array] = None) -> jax.Array:
    """Per-token relevance scores (b, n_kv, T) fp32, NEG_INF on invalid
    slots, for any scoring method.  ``q_valid`` (b, t) masks ragged-tail /
    pad query rows out of quoka's chunk statistics (the baselines keep
    their published scoring definitions and ignore it)."""
    with jax.named_scope("plan_scores"):
        valid = prior_context_valid(key_pos, chunk_start)
        if method == "quoka":
            q = qk.sanitize_queries(q, q_valid)
            qs = qk.subselect_queries(q, cfg.n_queries, n_kv=k.shape[2],
                                      q_valid=q_valid)
            return qk.quoka_scores(qs, k, valid, cfg)
        return sel_scores.compute_scores(method, q, k, valid, cfg)


# ----------------------------------------------------------------------------
# stage 2: select (top-k on the grid)
# ----------------------------------------------------------------------------

def plan_from_scores(scores: jax.Array, key_pos: jax.Array,
                     cfg: QuokaConfig,
                     budget: Optional[int] = None) -> SelectionPlan:
    """Top-k of token scores on the selection grid (Algorithm 1 line 11).

    scores: (b, n_kv, T) fp32 with NEG_INF on invalid slots; key_pos (b, T).
    Sink protection first force-keeps the ``keep_first`` earliest real
    tokens (their blocks, at g > 1) by stamping +inf onto valid slots.
    """
    with jax.named_scope("plan_topk"):
        return _plan_from_scores(scores, key_pos, cfg, budget)


def _plan_from_scores(scores, key_pos, cfg, budget):
    b, n_kv, t = scores.shape
    g = grid(cfg)
    budget = floor_to_grid(min(budget or sel_scores.resolve_budget(cfg, t),
                               t), g)
    if cfg.keep_first:
        sink = (key_pos >= 0) & (key_pos < cfg.keep_first)       # (b, T)
        scores = jnp.where(sink[:, None, :] & (scores > NEG_INF / 2),
                           jnp.inf, scores)
    if g == 1:
        top_s, top_i = jax.lax.top_k(scores, budget)             # (b,n_kv,B)
        good = top_s > NEG_INF / 2
        return SelectionPlan(idx=jnp.where(good, top_i, -1))
    if t % g:
        raise ValueError(
            f"selection granularity {g} must divide the cache view length "
            f"{t} (align granularity with the pool block size / B_CP)")
    # block score = max over the g tokens AND over KV heads: heads share
    # one plan (physical pool blocks hold every head's rows — a per-head
    # block plan could not be a contiguous sub-view of the block table)
    sb = scores.reshape(b, n_kv, t // g, g).max(axis=3).max(axis=1)
    top_s, top_i = jax.lax.top_k(sb, budget // g)                # (b, NB)
    good = top_s > NEG_INF / 2
    return SelectionPlan(idx=jnp.where(good, top_i, -1))


# ----------------------------------------------------------------------------
# stage 3: materialize (contiguous gather)
# ----------------------------------------------------------------------------

def materialize(plan: SelectionPlan, k, v, key_pos, chunk_start,
                cfg: QuokaConfig) -> Selected:
    """Gather a plan's KV budget from a cache view into a dense ``Selected``.

    k, v: (b, T, n_kv, d); key_pos: (b, T).  Validity is re-derived HERE
    (``prior_context_valid``), not trusted from build time: block-granular
    plans include boundary-straddling blocks whole and reused plans may be
    consumed under a different query chunk, so per-token selectability is a
    materialize-time property.  Tokens that are not selectable get
    ``pos == -1`` (budget padding), which downstream attention masks.

    At granularity g > 1 the gather moves whole (g, n_kv, d) slabs via a
    block-axis ``take_along_axis`` — XLA lowers this to a gather whose
    slice sizes span the full block extent (contiguous dynamic-slices over
    blocks, no per-token gather), the property the paged serving path
    relies on and the HLO suite asserts.
    """
    b, t, n_kv, d = k.shape
    g = grid(cfg)
    with jax.named_scope("plan_materialize"):
        return _materialize(plan, k, v, key_pos, chunk_start, cfg, b, t,
                            n_kv, d, g)


def _materialize(plan, k, v, key_pos, chunk_start, cfg, b, t, n_kv, d, g):
    valid = prior_context_valid(key_pos, chunk_start)            # (b, T)
    if g == 1:
        top_i = plan.idx                                         # (b,n_kv,B)
        safe = jnp.maximum(top_i, 0)
        # gather along the TIME axis directly — transposing the K/V caches
        # first would materialise a full-cache copy per chunk (§Perf A5)
        idx_t = safe.transpose(0, 2, 1)[..., None]               # (b,B,n_kv,1)
        k_sel = jnp.take_along_axis(k, idx_t, axis=1)            # (b,B,n_kv,d)
        v_sel = jnp.take_along_axis(v, idx_t, axis=1)
        shape = top_i.shape[:2] + (t,)
        pos = jnp.take_along_axis(
            jnp.broadcast_to(key_pos[:, None, :], shape), safe, axis=2)
        ok = jnp.take_along_axis(
            jnp.broadcast_to(valid[:, None, :], shape), safe, axis=2)
        good = (top_i >= 0) & ok
        return Selected(k=k_sel, v=v_sel, pos=jnp.where(good, pos, -1),
                        idx=jnp.where(good, top_i, -1))
    nb = plan.idx.shape[1]
    blocks = jnp.maximum(plan.idx, 0)                            # (b, NB)
    kb = k.reshape(b, t // g, g, n_kv, d)
    ib = blocks[:, :, None, None, None]
    k_sel = jnp.take_along_axis(kb, ib, axis=1).reshape(b, nb * g, n_kv, d)
    v_sel = jnp.take_along_axis(v.reshape(b, t // g, g, n_kv, d), ib,
                                axis=1).reshape(b, nb * g, n_kv, d)
    pos_sel = jnp.take_along_axis(key_pos.reshape(b, t // g, g),
                                  blocks[:, :, None], axis=1)    # (b, NB, g)
    ok_sel = jnp.take_along_axis(valid.reshape(b, t // g, g),
                                 blocks[:, :, None], axis=1)
    good = ok_sel & (plan.idx >= 0)[:, :, None]
    pos_flat = jnp.where(good, pos_sel, -1).reshape(b, nb * g)
    slot = blocks[:, :, None] * g + jnp.arange(g, dtype=jnp.int32)
    idx_flat = jnp.where(good, slot, -1).reshape(b, nb * g)
    # heads share the plan: broadcast the per-token metadata to the
    # Selected contract's per-head layout
    return Selected(
        k=k_sel, v=v_sel,
        pos=jnp.broadcast_to(pos_flat[:, None, :], (b, n_kv, nb * g)),
        idx=jnp.broadcast_to(idx_flat[:, None, :], (b, n_kv, nb * g)))


# ----------------------------------------------------------------------------
# fused entry points
# ----------------------------------------------------------------------------

def build(method: str, q, k, key_pos, chunk_start, cfg: QuokaConfig,
          budget: Optional[int] = None,
          q_valid: Optional[jax.Array] = None) -> SelectionPlan:
    """Stages 1+2: score the cache view and plan the top-k budget.

    For quoka under an active tensor-parallel sharding policy with an
    indivisible KV-head axis, scoring + candidate top-k run T-local per
    shard (``quoka.tp_plan_candidates``) and only plan indices cross the
    interconnect; materialize then runs on the replicated cache exactly as
    in the meshless path.
    """
    t = k.shape[1]
    budget = floor_to_grid(min(budget or sel_scores.resolve_budget(cfg, t),
                               t), grid(cfg))
    if method == "quoka":
        info = qk._tp_route(k, cfg)
        if info is not None:
            # scoring and the candidate top-k share one shard_map
            with jax.named_scope("plan_scores"):
                q = qk.sanitize_queries(q, q_valid)
                qs = qk.subselect_queries(q, cfg.n_queries, n_kv=k.shape[2],
                                          q_valid=q_valid)
                valid = prior_context_valid(key_pos, chunk_start)
                return SelectionPlan(idx=qk.tp_plan_candidates(
                    qs, k, key_pos, valid, cfg, budget, info))
    scores = plan_scores(method, q, k, key_pos, chunk_start, cfg,
                         q_valid=q_valid)
    return plan_from_scores(scores, key_pos, cfg, budget=budget)


def select(method: str, q, k, v, key_pos, chunk_start, cfg: QuokaConfig,
           budget: Optional[int] = None,
           q_valid: Optional[jax.Array] = None) -> Selected:
    """All three stages: the drop-in selection call for one-shot callers
    (``full`` must be handled by the caller — it means 'do not select')."""
    pln = build(method, q, k, key_pos, chunk_start, cfg, budget=budget,
                q_valid=q_valid)
    return materialize(pln, k, v, key_pos, chunk_start, cfg)


# ----------------------------------------------------------------------------
# cross-layer reuse
# ----------------------------------------------------------------------------

def empty_carry(shape) -> PlanCarry:
    """An invalid carry of the given ``plan_idx_shape`` — forces the first
    plan-capable layer to build."""
    return PlanCarry(idx=jnp.full(shape, -1, jnp.int32),
                     valid=jnp.zeros((), bool))


def _refresh_decision(carry: PlanCarry, layer_idx, cfg: QuokaConfig):
    """Traced () bool: does layer L rebuild?  (invalid carry, the interval
    grid, or a correction layer.)  Shared by the plain and obs refresh
    paths so the reuse schedule cannot drift between them."""
    s = max(1, cfg.reuse_interval)
    li = jnp.asarray(layer_idx, jnp.int32)
    do = (~carry.valid) | (li % s == 0)
    if cfg.correction_layers:
        corr = jnp.asarray(cfg.correction_layers, jnp.int32)
        do = do | jnp.any(li == corr)
    return do


def refresh(carry: Optional[PlanCarry], layer_idx, cfg: QuokaConfig,
            build_fn) -> tuple:
    """Per-layer reuse decision: (plan for this layer, updated carry).

    With no carry (reuse disabled / unsupported geometry) every layer
    builds.  Otherwise layer L rebuilds iff the carry is still invalid,
    L % reuse_interval == 0, or L is a correction layer; in between, the
    carried indices are reused as-is.  ``layer_idx`` is the traced GLOBAL
    layer index (models/stack.py computes it across stacks), so reuse runs
    span stack boundaries whenever the plan geometry matches.
    """
    if carry is None:
        return build_fn(), None
    do = _refresh_decision(carry, layer_idx, cfg)
    idx = jax.lax.cond(do, lambda: build_fn().idx, lambda: carry.idx)
    return SelectionPlan(idx=idx), PlanCarry(idx=idx,
                                             valid=jnp.ones((), bool))


# ----------------------------------------------------------------------------
# in-jit telemetry (the aux-stats pytree — see LayerObs)
# ----------------------------------------------------------------------------

def nan_obs() -> LayerObs:
    """The all-NaN LayerObs for layers that never select (recurrent /
    encoder blocks) — keeps the per-layer stats pytree uniform so the stack
    scan can stack it as ys."""
    n = jnp.full((), jnp.nan, jnp.float32)
    return LayerObs(n, n, n, n)


def dense_obs(key_pos, chunk_start) -> LayerObs:
    """LayerObs for a dense (no-selection) attention layer: every selectable
    prior token is attended, there is no budget and no score pass."""
    valid = prior_context_valid(key_pos, chunk_start)
    ctxc = jnp.mean(jnp.sum(valid, axis=-1).astype(jnp.float32))
    n = jnp.full((), jnp.nan, jnp.float32)
    return LayerObs(sel_tokens=ctxc, ctx_tokens=ctxc, budget_tokens=n,
                    refreshed=jnp.zeros((), jnp.float32))


def selected_obs(sel_pos, key_pos, chunk_start, budget: int,
                 refreshed) -> LayerObs:
    """LayerObs for a selecting layer, from the materialized budget's
    validity (``sel_pos == -1`` marks padding — exactly what downstream
    attention masks, so sel_tokens counts KV pairs actually attended)."""
    selc = jnp.mean(jnp.sum(sel_pos >= 0, axis=-1).astype(jnp.float32))
    valid = prior_context_valid(key_pos, chunk_start)
    ctxc = jnp.mean(jnp.sum(valid, axis=-1).astype(jnp.float32))
    return LayerObs(sel_tokens=selc, ctx_tokens=ctxc,
                    budget_tokens=jnp.full((), float(budget), jnp.float32),
                    refreshed=jnp.asarray(refreshed, jnp.float32))


def refreshed_flag(carry: Optional[PlanCarry], layer_idx,
                   cfg: QuokaConfig) -> jax.Array:
    """() f32: 1.0 if ``refresh`` builds at this layer, 0.0 if it reuses
    the carried plan (the ``LayerObs.refreshed`` counter)."""
    if carry is None:
        return jnp.ones((), jnp.float32)
    return _refresh_decision(carry, layer_idx, cfg).astype(jnp.float32)


def select_with_ctx(ctx, plan, method: str, q, k, v, key_pos, chunk_start,
                    cfg: QuokaConfig, budget: Optional[int] = None,
                    q_valid: Optional[jax.Array] = None):
    """The block-facing selection entry: refresh-or-build + materialize.

    Returns (Selected, updated plan carry).  When ``ctx["obs"]`` is set,
    the layer's ``LayerObs`` is left in ``ctx["_obs"]`` for the stack scan
    body to pop (the MoE aux-loss side-channel pattern — ``ctx`` is already
    a per-layer copy whenever obs is on, see models/stack.py).  When obs is
    off this is byte-identical to the refresh + materialize it replaced.
    """
    li = ctx.get("layer_idx", 0)
    obs = ctx.get("obs")
    refreshed = refreshed_flag(plan, li, cfg) if obs else None
    pln, plan = refresh(
        plan, li, cfg,
        lambda: build(method, q, k, key_pos, chunk_start, cfg,
                      budget=budget, q_valid=q_valid))
    sel = materialize(pln, k, v, key_pos, chunk_start, cfg)
    if obs:
        t = k.shape[1]
        bud = floor_to_grid(
            min(budget or sel_scores.resolve_budget(cfg, t), t), grid(cfg))
        ctx["_obs"] = selected_obs(sel.pos, key_pos, chunk_start, bud,
                                   refreshed)
    _note_block_counts(ctx, pln, cfg)
    return sel, plan


def _note_block_counts(ctx, pln: SelectionPlan, cfg: QuokaConfig) -> None:
    """Leave this layer's ``pool_block_counts`` in ``ctx["_selblk"]`` when
    the caller asked for the prefetch-oracle side channel
    (``ctx["selblk"] = (block_size, n_blocks)``) — same pop-from-ctx
    pattern as ``ctx["_obs"]``; models/stack.py collects it as scan ys."""
    sb = ctx.get("selblk") if isinstance(ctx, dict) else None
    if sb is not None:
        ctx["_selblk"] = pool_block_counts(pln, cfg, sb[0], sb[1])


# ----------------------------------------------------------------------------
# gather-free fused path (kernels/selected_attention.py)
# ----------------------------------------------------------------------------

def pool_block_counts(plan: SelectionPlan, cfg: QuokaConfig,
                      block_size: int, n_blocks: int) -> jax.Array:
    """(b, n_blocks) int32: how many of this plan's selected entries land
    in each LOGICAL pool block of the request's cache view — the plan's
    indices read off BEFORE materialize, which is what makes QUOKA's
    stage-2 output double as the host-tier prefetch oracle (the engine
    aggregates these into a per-logical-offset hotness ranking that orders
    which demoted blocks to stage first; see serving/engine.py).

    Token plans (g == 1) map slots to blocks by division; block plans map
    grid ids through the grid/block ratio.  Padding (-1) drops."""
    g = grid(cfg)
    idx = plan.idx
    if g == 1:
        flat = idx.reshape(idx.shape[0], -1)       # (b, n_kv * B) slots
        ids = flat // block_size
    else:
        flat = idx                                  # (b, NB) grid ids
        ids = (flat * g) // block_size
    ids = jnp.where(flat >= 0, ids, n_blocks)      # padding -> out of range
    rows = jnp.arange(ids.shape[0], dtype=jnp.int32)[:, None]
    return jnp.zeros((ids.shape[0], n_blocks), jnp.int32).at[rows, ids].add(
        1, mode="drop")


def fused_route(cfg: QuokaConfig, method: str, k,
                window: Optional[int] = None) -> bool:
    """Static dispatch rule: may the gather-free fused selected-attention
    kernel replace the staged materialize + attend pair for this call site?

    The fused kernel streams whole (g, n_kv, d) slabs through its index
    maps, so it serves exactly the geometries where that is well-defined:

      * ``cfg.fused_select_attn`` opted in (default off — the staged path
        stays the baseline and every bit-exactness suite keeps its oracle);
      * block-granular plans only (granularity > 1, head-shared ids) whose
        grid divides the cache view;
      * no sliding window (the per-query window constraint cannot be
        expressed by the kernel's static boundary + per-key masks) — MLA's
        latent-space selection never reaches this router at all;
      * no active mesh policy: pallas_call under GSPMD partitioning (and
        the TP T-local scoring route) stays on the staged path.
    """
    if not getattr(cfg, "fused_select_attn", False):
        return False
    if window is not None:
        return False
    g = grid(cfg)
    if g <= 1:
        return False                      # token-slot plans stay staged
    if k.shape[1] % g:
        return False
    if shctx.get_policy()[0] is not None:
        return False
    return True


def plan_selected_pos(plan: SelectionPlan, key_pos, chunk_start,
                      cfg: QuokaConfig) -> jax.Array:
    """Positions-only twin of ``materialize`` for telemetry: the selected
    positions (-1 = padding) with validity re-derived exactly as
    materialize derives it, WITHOUT touching K/V.  The fused kernel applies
    the same masks in-kernel; this keeps ``LayerObs.sel_tokens`` exact
    while gathering only the (b, T) int32 positions — bytes, not the KV
    budget the fused path exists to avoid."""
    b, t = key_pos.shape
    g = grid(cfg)
    valid = prior_context_valid(key_pos, chunk_start)
    if g == 1:
        top_i = plan.idx                                     # (b, n_kv, B)
        safe = jnp.maximum(top_i, 0)
        shape = top_i.shape[:2] + (t,)
        pos = jnp.take_along_axis(
            jnp.broadcast_to(key_pos[:, None, :], shape), safe, axis=2)
        ok = jnp.take_along_axis(
            jnp.broadcast_to(valid[:, None, :], shape), safe, axis=2)
        return jnp.where((top_i >= 0) & ok, pos, -1)
    blocks = jnp.maximum(plan.idx, 0)                        # (b, NB)
    pos_sel = jnp.take_along_axis(key_pos.reshape(b, t // g, g),
                                  blocks[:, :, None], axis=1)
    ok_sel = jnp.take_along_axis(valid.reshape(b, t // g, g),
                                 blocks[:, :, None], axis=1)
    good = ok_sel & (plan.idx >= 0)[:, :, None]
    return jnp.where(good, pos_sel, -1).reshape(b, 1, -1)


def fused_attend_with_ctx(ctx, plan, method: str, q, k, v, key_pos,
                          chunk_start, cfg: QuokaConfig,
                          budget: Optional[int] = None,
                          q_valid: Optional[jax.Array] = None):
    """Fused twin of ``select_with_ctx`` + the block's staged attention:
    refresh-or-build the plan, then attend straight THROUGH its indices via
    ``kops.selected_attention`` — no materialize, no [budget | chunk]
    concat, one kernel launch.  Callers gate on ``fused_route``.

    Returns (att (b, t, h, d), updated plan carry); the obs side-channel
    contract matches select_with_ctx (``ctx["_obs"]`` from the positions-
    only gather, so telemetry stays exact without the KV round-trip).
    """
    li = ctx.get("layer_idx", 0)
    be = ctx.get("backend")
    g = grid(cfg)
    t = k.shape[1]
    bud = floor_to_grid(min(budget or sel_scores.resolve_budget(cfg, t), t),
                        g)
    obs = ctx.get("obs")
    refreshed = refreshed_flag(plan, li, cfg) if obs else None
    pln, plan = refresh(
        plan, li, cfg,
        lambda: build(method, q, k, key_pos, chunk_start, cfg,
                      budget=bud, q_valid=q_valid))
    att = kops.selected_attention(q, k, v, key_pos, pln.idx, chunk_start,
                                  granularity=g, backend=be, cfg=cfg)
    if obs:
        ctx["_obs"] = selected_obs(
            plan_selected_pos(pln, key_pos, chunk_start, cfg), key_pos,
            chunk_start, bud, refreshed)
    _note_block_counts(ctx, pln, cfg)
    return att, plan
