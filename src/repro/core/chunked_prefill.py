"""Standalone chunked-prefill sparse attention (paper Algorithm 2) at the
single-attention-layer level.

Given the full-sequence Q, K, V of one layer, simulate chunked prefill with
any selection method and return the attention outputs for every position.
This is the apples-to-apples harness behind the accuracy-proxy benchmarks
(paper Tables 1/3 proxies) and the equivalence property tests
(budget >= T  ==>  output == dense causal attention).

The full model path lives in models/model.py::Model.prefill; this module is
deliberately model-free.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import QuokaConfig
from repro.core import plan as plan_mod
from repro.core.attention import attention_with_positions
from repro.kernels import ops as kops


def dense_causal_reference(q, k, v):
    """Oracle: full causal attention.  q (b,T,h,d), k/v (b,T,n_kv,d)."""
    b, t = q.shape[:2]
    pos = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    return attention_with_positions(q, k, v, pos, pos, causal=True)


def chunked_sparse_attention(q, k, v, cfg: QuokaConfig,
                             method: Optional[str] = None,
                             unroll: bool = False,
                             backend: Optional[str] = None):
    """Chunked prefill with per-chunk KV selection.

    q: (b, T, h, d); k, v: (b, T, n_kv, d); T % cfg.chunk_size == 0.
    ``backend`` explicitly pins the kernel backend (outranks the
    REPRO_BACKEND env var and ``cfg.backend``; see kernels/ops.py).
    Returns (b, T, h, d) attention outputs (softmax over the selected set —
    the quantity eq. (4) asks ``f`` to preserve).
    """
    import dataclasses

    method = method or cfg.method
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    bcp = min(cfg.chunk_size, t)
    assert t % bcp == 0
    nc = t // bcp
    pos_all = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)

    if method == "full":
        return dense_causal_reference(q, k, v)

    # resolve once and bake into cfg so the scoring stage (inside
    # sel_mod.select) dispatches consistently with the attention stage
    backend = kops.resolve_backend(backend, cfg)
    cfg = dataclasses.replace(cfg, backend=backend)
    qs = q.reshape(b, nc, bcp, h, d).swapaxes(0, 1)
    ks = k.reshape(b, nc, bcp, n_kv, d).swapaxes(0, 1)
    vs = v.reshape(b, nc, bcp, n_kv, d).swapaxes(0, 1)
    ps = pos_all.reshape(b, nc, bcp).swapaxes(0, 1)

    fused = plan_mod.fused_route(cfg, method, k)

    def one_chunk(i, qc, kc, vc, pc):
        start = pc[0, 0]
        if fused:
            # gather-free path: build the plan and attend straight through
            # its block ids (kernels/selected_attention.py) — the chunk KV
            # is read from the full cache view at [start, start + B_CP)
            pln = plan_mod.build(method, qc, k, pos_all, start, cfg)
            return kops.selected_attention(
                qc, k, v, pos_all, pln.idx, start,
                granularity=plan_mod.grid(cfg), backend=backend, cfg=cfg)
        # the staged plan pipeline (score -> select -> materialize); block
        # plans include boundary-straddling blocks whole and re-mask their
        # not-yet-prior tokens inside materialize
        sel = plan_mod.select(method, qc, k, v, pos_all, start, cfg)
        # [selected budget | chunk] layout: the budget is an unconditioned
        # prefix (every gathered key is strictly before the chunk by
        # construction), the chunk is causal w.r.t. chunk-local indices —
        # exactly the flash kernel's static `boundary` mask, with budget
        # padding masked via per-KV-head k_valid (``sel.valid``).
        k_cat = jnp.concatenate([sel.k, kc], axis=1)
        v_cat = jnp.concatenate([sel.v, vc], axis=1)
        k_valid = jnp.concatenate(
            [sel.valid, jnp.ones((b, n_kv, bcp), bool)], axis=-1)
        return kops.attention(qc, k_cat, v_cat, k_valid, causal=True,
                              boundary=sel.pos.shape[-1], backend=backend)

    if unroll:
        outs = [one_chunk(i, qs[i], ks[i], vs[i], ps[i]) for i in range(nc)]
        out = jnp.stack(outs)
    else:
        def body(_, inp):
            i, qc, kc, vc, pc = inp
            return None, one_chunk(i, qc, kc, vc, pc)
        _, out = jax.lax.scan(
            body, None, (jnp.arange(nc), qs, ks, vs, ps))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


def output_error(q, k, v, cfg: QuokaConfig, method: str) -> jax.Array:
    """Relative L2 error vs the dense-causal oracle (paper eq. (4))."""
    ref = dense_causal_reference(q, k, v)
    out = chunked_sparse_attention(q, k, v, cfg, method)
    num = jnp.linalg.norm((out - ref).astype(jnp.float32))
    den = jnp.linalg.norm(ref.astype(jnp.float32)) + 1e-9
    return num / den


def _oracle_probs(q, k, start, pos_all):
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    qc = q[:, start:]
    mask = (pos_all[:, None, None, :] < start)
    kr = jnp.repeat(k, h // n_kv, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", qc.astype(jnp.float32),
                        kr.astype(jnp.float32)) / jnp.sqrt(float(d))
    logits = jnp.where(mask, logits, -1e30)
    return jax.nn.softmax(logits, axis=-1)          # (b, h, chunk, T)


def key_recall(q, k, v, cfg: QuokaConfig, method: str,
               oracle: str = "max") -> jax.Array:
    """Fraction of the oracle's true top-B keys that the method selects
    (last chunk, the hardest selection).

    oracle="max": per-key criticality = max over chunk queries of the
    softmax prob — 'is this key decisive for ANY query', the NIAH/RULER
    criterion and eq-(4)'s worst case.  oracle="mean": summed mass (biased
    toward what mean-aggregating scorers compute; reported for contrast)."""
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    bcp = min(cfg.chunk_size, t)
    pos_all = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    start = t - bcp
    sel = plan_mod.select(method, q[:, start:], k, v, pos_all,
                          jnp.asarray(start), cfg)
    probs = _oracle_probs(q, k, start, pos_all)
    agg = probs.max(axis=2) if oracle == "max" else probs.sum(axis=2)
    mass = agg.reshape(b, n_kv, h // n_kv, t).max(axis=2) if oracle == "max" \
        else agg.reshape(b, n_kv, h // n_kv, t).mean(axis=2)
    budget = sel.pos.shape[-1]
    _, true_top = jax.lax.top_k(mass, budget)                # (b, n_kv, B)
    sel_pos = sel.pos
    hit = (sel_pos[..., :, None] == true_top[..., None, :]).any(-1)
    valid = sel_pos >= 0
    return (hit & valid).sum() / true_top.size


def critical_key_recall(q, k, v, cfg: QuokaConfig, method: str,
                        tau: float = 0.08) -> jax.Array:
    """Recall over CRITICAL keys only: keys that receive >= tau softmax prob
    from at least one chunk query (the needle criterion).  Uncritical keys
    are excluded from the denominator, so diffuse bulk mass cannot reward a
    selector — this is the direct NIAH-mechanism proxy."""
    b, t, h, d = q.shape
    n_kv = k.shape[2]
    bcp = min(cfg.chunk_size, t)
    pos_all = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    start = t - bcp
    sel = plan_mod.select(method, q[:, start:], k, v, pos_all,
                          jnp.asarray(start), cfg)
    probs = _oracle_probs(q, k, start, pos_all)              # (b,h,c,T)
    crit = probs.max(axis=2).reshape(b, n_kv, h // n_kv, t).max(axis=2) >= tau
    sel_mask = jnp.zeros((b, n_kv, t), bool)
    bidx = jnp.arange(b)[:, None, None]
    hidx = jnp.arange(n_kv)[None, :, None]
    safe_idx = jnp.clip(sel.idx, 0, t - 1)
    sel_mask = sel_mask.at[bidx, hidx, safe_idx].set(sel.idx >= 0)
    hits = (crit & sel_mask).sum()
    return hits / jnp.maximum(crit.sum(), 1)
