"""Framework-facing kernel dispatch facade.

Every attention/scoring call in the system (chunked prefill, the serving
engine, the standalone accuracy harness, benchmarks) goes through the two
entry points here instead of hand-rolling masks + ``dense_attention``:

  * ``attention(q, k, v, k_valid, causal=, boundary=, backend=)`` —
    Algorithm 2's post-selection attention.  The first ``boundary`` keys are
    an unconditioned prefix (the gathered selection budget, all strictly
    before the chunk by construction), the remaining keys are causal with
    respect to chunk-local indices; ``k_valid`` masks budget padding and may
    be per-KV-head ((b, n_kv, tk)) since gathered budgets differ per head.
    ``boundary=0`` is plain causal attention; ``causal=False`` is dense
    cross attention.
  * ``score(qbar, k, valid, backend=)`` — Algorithm 1's fused scoring pass
    (normalise K -> Q̄Kᵀ -> max over queries -> validity mask).

Dispatch contract
-----------------
``backend`` is one of:

  "xla"              pure-jnp reference (kernels/ref.py) — fast on CPU,
                     compiles anywhere, the parity oracle.
  "pallas_interpret" the Pallas kernels run under ``interpret=True`` —
                     slow, exercises the exact kernel code path on any
                     backend (used by the parity/allclose suites).
  "pallas"           compiled Pallas TPU kernels.
  "auto" / None      resolve via `resolve_backend`.

``resolve_backend(backend, cfg)`` picks, in priority order:
  1. an explicit non-"auto" ``backend`` argument,
  2. the ``REPRO_BACKEND`` environment variable (global override),
  3. ``QuokaConfig.backend`` when not "auto",
  4. hardware auto-detection: "pallas" on TPU, else "xla".
The model resolves once and stores the result in its ``QuokaConfig``;
QUOKA scoring passes that as the explicit argument, so the environment
cannot switch a resolved call.

All backends produce outputs equal within tolerance (enforced by
tests/test_backend_parity.py); layout conversion BTHD <-> BHTD happens here.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhtd
from repro.kernels.quoka_score import quoka_score_bhtd
from repro.kernels.selected_attention import (selected_attention_bhtd,
                                              selected_attention_paged)
from repro.sharding import ctx as shctx
from repro.sharding.specs import _maybe

BACKENDS = ("xla", "pallas_interpret", "pallas")
_ENV_VAR = "REPRO_BACKEND"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: Optional[str] = None, cfg=None) -> str:
    """Resolve a backend name per the module-docstring priority order.

    ``cfg`` is a ``QuokaConfig`` (or anything with a ``backend`` attribute);
    the result is always a concrete member of ``BACKENDS``.
    """
    be = backend or "auto"
    if be == "auto":
        be = os.environ.get(_ENV_VAR, "auto")
    if be == "auto" and cfg is not None:
        be = getattr(cfg, "backend", "auto") or "auto"
    if be == "auto":
        be = "pallas" if _on_tpu() else "xla"
    if be not in BACKENDS:
        raise ValueError(f"unknown kernel backend {be!r}; "
                         f"expected one of {BACKENDS + ('auto',)}")
    return be


def _per_device(kernel, args, heads_dims, out_ndim: int):
    """Call a Pallas ``kernel`` (BHTD layouts, batch on dim 0, heads on dim
    ``heads_dims[i]`` of each arg and dim 1 of the output).

    GSPMD cannot partition a Mosaic kernel, so under an active mesh policy
    (sharding/ctx.py) the call runs inside a ``shard_map``: each device
    takes its own batch rows (the FSDP axes) and heads (the ``model`` axis
    where every heads dim divides it, else all heads).  Off-mesh, and in
    the body of an enclosing ``shard_map`` (already per-device), it is a
    plain call."""
    mesh, b_axes, m_ax = shctx.get_policy()
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(*args)
    b_ax = _maybe(mesh, args[0].shape[0], b_axes or None)
    h_ax = m_ax if all(_maybe(mesh, x.shape[hd], m_ax)
                       for x, hd in zip(args, heads_dims)
                       if hd is not None) else None

    def spec(ndim, hd):
        s = [b_ax] + [None] * (ndim - 1)
        if hd is not None:
            s[hd] = h_ax
        return P(*s)

    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=tuple(spec(x.ndim, hd) for x, hd in zip(args, heads_dims)),
        out_specs=spec(out_ndim, 1), check_vma=False)(*args)


def attention(q, k, v, k_valid=None, *, causal: bool = True,
              boundary: int = 0, scale: Optional[float] = None,
              backend: Optional[str] = None, cfg=None):
    """Post-selection attention over a [selected budget | chunk] key layout.

    q: (b, tq, h, d); k, v: (b, tk, h_kv, d);
    k_valid: bool (b, tk) or (b, h_kv, tk) — False keys never attended.
    ``boundary`` (static) marks the selected-prefix length.
    Returns (b, tq, h, d).
    """
    be = resolve_backend(backend, cfg)
    # trace-time profiler marker: zero runtime cost, attributes the fused
    # ops to this region in jax.profiler / HLO metadata
    with jax.named_scope(f"ops_attention_{be}"):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        if be == "xla":
            out = ref.flash_attention_ref(qt, kt, vt, causal=causal,
                                          boundary=boundary, k_valid=k_valid,
                                          scale=scale)
        else:
            if k_valid is None:
                k_valid = jnp.ones((q.shape[0], k.shape[1]), bool)
            kernel = functools.partial(
                flash_attention_bhtd, causal=causal, boundary=boundary,
                scale=scale, interpret=(be != "pallas"))
            out = _per_device(kernel, (qt, kt, vt, k_valid),
                              (1, 1, 1, 1 if k_valid.ndim == 3 else None), 4)
        return out.transpose(0, 2, 1, 3)


def _selected_xla(q, k, v, key_pos, plan_idx, chunk_start, g, scale):
    """Parity oracle for the fused kernel: materialize the plan with
    ``take_along_axis`` (the staged path's gather, re-implemented locally —
    ops must not import core.plan), slice the chunk rows out of the cache,
    and run ``flash_attention_ref`` over the [budget | chunk] concat."""
    b, T, n_kv, d = k.shape
    t = q.shape[1]
    start = jnp.asarray(chunk_start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start[None], (b,))
    valid = (key_pos >= 0) & (key_pos < start[:, None])          # (b, T)
    idx = plan_idx.astype(jnp.int32)
    if g == 1:
        if idx.ndim == 2:
            idx = jnp.broadcast_to(idx[:, None, :], (b, n_kv, idx.shape[1]))
        safe = jnp.maximum(idx, 0)
        idx_t = safe.transpose(0, 2, 1)[..., None]               # (b,B,n_kv,1)
        k_sel = jnp.take_along_axis(k, idx_t, axis=1)
        v_sel = jnp.take_along_axis(v, idx_t, axis=1)
        shape = idx.shape[:2] + (T,)
        pos = jnp.take_along_axis(
            jnp.broadcast_to(key_pos[:, None, :], shape), safe, axis=2)
        ok = jnp.take_along_axis(
            jnp.broadcast_to(valid[:, None, :], shape), safe, axis=2)
        sel_valid = (idx >= 0) & ok & (pos >= 0)                 # (b,n_kv,B)
    else:
        if idx.ndim == 3:
            idx = idx[:, 0]               # block plans are head-shared
        nb = idx.shape[1]
        blocks = jnp.maximum(idx, 0)
        ib = blocks[:, :, None, None, None]
        k_sel = jnp.take_along_axis(
            k.reshape(b, T // g, g, n_kv, d), ib,
            axis=1).reshape(b, nb * g, n_kv, d)
        v_sel = jnp.take_along_axis(
            v.reshape(b, T // g, g, n_kv, d), ib,
            axis=1).reshape(b, nb * g, n_kv, d)
        ok_sel = jnp.take_along_axis(valid.reshape(b, T // g, g),
                                     blocks[:, :, None], axis=1)
        good = (ok_sel & (idx >= 0)[:, :, None]).reshape(b, nb * g)
        sel_valid = jnp.broadcast_to(good[:, None, :], (b, n_kv, nb * g))
    boundary = k_sel.shape[1]
    # chunk rows are CONTIGUOUS in the cache view (the chunk contract puts
    # them at [start, start + t), start <= T - t), so a clamped dynamic
    # slice replaces a per-row gather — the same access the fused kernel's
    # chunk-walk tiles make
    slc = jax.vmap(lambda x, s: jax.lax.dynamic_slice_in_dim(x, s, t, 0))
    k_chunk = slc(k, start)
    v_chunk = slc(v, start)
    cpos = slc(key_pos, start)                                   # (b, t)
    chunk_valid = jnp.broadcast_to((cpos >= 0)[:, None, :], (b, n_kv, t))
    k_valid = jnp.concatenate([sel_valid, chunk_valid], axis=-1)
    out = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3),
        jnp.concatenate([k_sel, k_chunk], axis=1).transpose(0, 2, 1, 3),
        jnp.concatenate([v_sel, v_chunk], axis=1).transpose(0, 2, 1, 3),
        causal=True, boundary=boundary, k_valid=k_valid, scale=scale)
    return out.transpose(0, 2, 1, 3)


def _linearize_pool(k_pool, v_pool, pos_pool, table):
    """Pool leaves -> per-request linear view (the xla oracle's stand-in
    for the index-map block-table composition).  Unmapped table slots read
    as empty blocks (pos == -1), mirroring serving/pool.py::gather."""
    b, nb_t = table.shape
    bs, n_kv, d = k_pool.shape[1:]
    safe = jnp.maximum(table, 0)
    k_lin = k_pool[safe].reshape(b, nb_t * bs, n_kv, d)
    v_lin = v_pool[safe].reshape(b, nb_t * bs, n_kv, d)
    pos_lin = jnp.where((table >= 0)[:, :, None], pos_pool[safe],
                        -1).reshape(b, nb_t * bs)
    return k_lin, v_lin, pos_lin


def selected_attention(q, k, v, key_pos, plan_idx, chunk_start, *,
                       granularity: int = 1, scale: Optional[float] = None,
                       backend: Optional[str] = None, cfg=None,
                       table=None, block_size: int = 0):
    """Gather-free fused twin of ``plan.materialize`` + ``attention``: one
    [selected-prefix | causal-chunk] attention straight off the
    ``SelectionPlan`` indices, with validity re-derived inside the kernel.

    q: (b, t, h, d) chunk queries (BTHD).
    Linear cache view (default): k, v (b, T, n_kv, d); key_pos (b, T).
    Paged pool view (``table`` given): k, v (N, block_size, n_kv, d) pool
      leaves, key_pos (N, block_size), table (b, nb_logical) with -1 =
      unmapped — the kernel attends THROUGH the block table.
    plan_idx: (b, B//g) block ids at granularity g > 1; (b, n_kv, B) token
      slots at g == 1.  chunk_start: () or (b,).
    Returns (b, t, h, d).

    Dispatch: "xla" is the parity oracle (take_along_axis materialize +
    flash_attention_ref — it DOES gather, by design); "pallas_interpret" /
    "pallas" run the scalar-prefetch Pallas kernel
    (kernels/selected_attention.py) with zero intermediate HBM traffic.
    """
    be = resolve_backend(backend, cfg)
    with jax.named_scope(f"ops_selected_attention_{be}"):
        if table is not None:
            if be == "xla":
                k, v, key_pos = _linearize_pool(k, v, key_pos, table)
                return _selected_xla(q, k, v, key_pos, plan_idx,
                                     chunk_start, granularity, scale)
            out = selected_attention_paged(
                q.transpose(0, 2, 1, 3), k, v, key_pos, plan_idx,
                chunk_start, table, granularity=granularity,
                block_size=block_size, scale=scale,
                interpret=(be != "pallas"))
            return out.transpose(0, 2, 1, 3)
        if be == "xla":
            return _selected_xla(q, k, v, key_pos, plan_idx, chunk_start,
                                 granularity, scale)
        out = selected_attention_bhtd(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), key_pos, plan_idx, chunk_start,
            granularity=granularity, scale=scale,
            interpret=(be != "pallas"))
        return out.transpose(0, 2, 1, 3)


def _score_xla(qbar, k, valid):
    """Production XLA twin of the fused scoring kernel, in BTHD layout.

    FUSED key normalisation (§Perf A1): scores are divided by per-key norms
    instead of materialising a normalised (fp32!) copy of the whole K cache
    — K is streamed once, in its storage dtype, by a single einsum; the
    self-dot runs bf16-reads/fp32-accumulate so no converted K copy is ever
    materialised (an astype(f32) here caused XLA to hoist a full-cache f32
    conversion across the prefill loop).  This is also the per-shard body
    of the T-local sharded scoring path (core/quoka.py), which is why it
    lives behind the facade: every shard of the mesh and the meshless
    fallback compute byte-identical score elements.
    """
    s = jnp.einsum("bnkd,btkd->bknt", qbar.astype(k.dtype), k,
                   preferred_element_type=jnp.float32)        # (b,n_kv,N_Q,t)
    sq = jnp.einsum("btkd,btkd->btk", k, k,
                    preferred_element_type=jnp.float32)
    inv = jax.lax.rsqrt(sq + 1e-16)                           # (b,t,n_kv)
    s = s * inv.transpose(0, 2, 1)[:, :, None, :]
    s = jnp.max(s, axis=2)
    return jnp.where(valid[:, None, :], s, ref.NEG_INF)


@functools.lru_cache(maxsize=32)
def score_projection(d: int, r: int, seed: int = 7) -> jax.Array:
    """Cached low-rank scoring projection (d, r).

    A fixed JL-style random projection stands in for the offline PCA of
    Loki / the `score_proj_dim` ablation (documented in selection.py).  The
    cache makes the projection a per-process constant: repeated chunks,
    decode steps and every layer of a stack reuse one array instead of
    re-deriving it per call (the old ``loki_scores`` rebuilt it on every
    chunk of every layer).

    The array is materialised under ``ensure_compile_time_eval`` so the
    cached value is always CONCRETE: the first call may happen inside a
    jit/scan trace (chunked prefill builds plans inside the scan body),
    and caching a tracer there would leak it into every later trace.
    """
    with jax.ensure_compile_time_eval():
        return jax.random.normal(jax.random.PRNGKey(seed), (d, r),
                                 jnp.float32) / jnp.sqrt(float(r))


def score(qbar, k, valid, *, backend: Optional[str] = None, cfg=None,
          proj: Optional[jax.Array] = None):
    """Fused QUOKA scoring (Algorithm 1 lines 7-10): cosine scores of
    pre-aggregated queries against normalised keys, max over the query axis.

    qbar: (b, n_q, n_kv, d) pre-aggregated NORMALISED queries (BTHD-ish);
    k: (b, t, n_kv, d) raw keys; valid: (b, t).
    Returns fp32 scores (b, n_kv, t) with NEG_INF on invalid slots.

    ``proj`` (d, r) optionally projects BOTH operands to a low-rank space
    before dispatch (`QuokaConfig.score_proj_dim`): the unchanged kernel
    then runs at head dim r, normalising the PROJECTED keys, so scores are
    cosines in the projected space.  Applying the projection here — above
    the backend split — keeps the xla and pallas branches twins for free.

    The keys may be any contiguous slice of a cache (scoring is local in
    the key axis), which is what the sharded T-local selection path relies
    on: each mesh shard scores only the keys it owns through this same
    entry point (projecting a slice == slicing the projected cache, so the
    low-rank mode composes with it exactly).
    """
    be = resolve_backend(backend, cfg)
    with jax.named_scope(f"ops_score_{be}"):
        if proj is not None:
            qbar = (qbar.astype(jnp.float32) @ proj)
            # project K in its storage dtype — an fp32 projected copy of the
            # cache would hoist a full-cache conversion (see _score_xla note)
            k = k @ proj.astype(k.dtype)
        if be == "xla":
            return _score_xla(qbar, k, valid)
        qt = qbar.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        kernel = functools.partial(quoka_score_bhtd,
                                   interpret=(be != "pallas"))
        return _per_device(kernel, (qt, kt, valid), (1, 1, None), 3)


# ---------------------------------------------------------------------------
# back-compat aliases (pre-facade names; "auto" keeps the old TPU-detection
# behaviour because resolve_backend falls through to hardware detection)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, k_valid=None, *, causal: bool = True,
                    boundary: int = 0, scale: Optional[float] = None,
                    backend: str = "auto"):
    return attention(q, k, v, k_valid, causal=causal, boundary=boundary,
                     scale=scale, backend=backend)


def quoka_score(qbar, k, valid, *, backend: str = "auto"):
    return score(qbar, k, valid, backend=backend)
