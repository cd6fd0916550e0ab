"""Blockwise (flash) attention Pallas TPU kernel.

Target: TPU MXU — (block_q × d) @ (d × block_k) tiles streamed HBM→VMEM with
an online-softmax carry (m, l, acc) in VMEM scratch across the innermost
(arbitrary-order) grid dimension.  Validated on CPU with interpret=True
against kernels/ref.py::flash_attention_ref.

Mask semantics match QUOKA's post-selection attention: the first
``boundary`` keys are an unconditioned prefix (the selected KV budget),
the remaining keys are causal with respect to chunk-local indices:

    attend(i, j) iff k_valid[j] and (not causal or j < boundary
                                     or j - boundary <= i)

With boundary=0 this is plain causal attention (training); with
causal=False it is a dense cross-attention.

Mosaic tiling: every block's last two dims are multiples of (8, 128) or
equal the array's own.  Key validity therefore travels as int32 with a
unit second-minor axis, ``(b, h_kv, 1, tk)`` in ``(1, 1, 1, block_k)``
blocks, and the row statistics m / l are ``(block_q, 1)`` columns — no
bool and no 1-D vector reaches the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
            m_ref, l_ref, acc_ref,
            *, scale: float, causal: bool, boundary: int,
            block_q: int, block_k: int, n_k: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    kb = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    vb = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    iq = pl.program_id(2)
    qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = valid_ref[0, 0] != 0                           # (1, bk)
    if causal:
        mask = mask & ((kj < boundary) | ((kj - boundary) <= qi))

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))  # (bq, 1)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # explicit re-mask
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = jnp.where(l > 0, acc_ref[...] / safe, 0.0
                                ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "boundary", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_bhtd(q, k, v, k_valid=None, *, causal: bool = True,
                         boundary: int = 0, scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: bool = True):
    """q: (b, h, tq, d); k, v: (b, h_kv, tk, d); k_valid: bool, either
    (b, tk) shared across heads or (b, h_kv, tk) per-KV-head (gathered
    selection budgets differ per KV head).  Shapes are padded to block
    multiples internally.

    ``block_q`` / ``block_k`` = None resolve through the autotuner's tuning
    table (kernels/autotune.py: exact-key table hit, else the deterministic
    128/128 defaults — the pre-autotuner constants), at trace time."""
    b, h, tq, d = q.shape
    h_kv, tk = k.shape[1], k.shape[2]
    g = h // h_kv
    scale = (d ** -0.5) if scale is None else scale

    tuned = None
    if block_q is None or block_k is None:
        tuned = autotune.lookup("flash_attention", t=tk, d=d, n_kv=h_kv,
                                budget=boundary, g=1)
        block_q = block_q or tuned["block_q"]
        block_k = block_k or tuned["block_k"]
    semantics = tuple(tuned["dimension_semantics"]) if tuned else \
        ("parallel", "parallel", "parallel", "arbitrary")
    block_q = min(block_q, max(8, 1 << (tq - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (tk - 1).bit_length()))
    pq = (-tq) % block_q
    pk = (-tk) % block_k
    pd = (-d) % 128 if not interpret else 0
    if k_valid is None:
        k_valid = jnp.ones((b, h_kv, tk), bool)
    elif k_valid.ndim == 2:
        k_valid = jnp.broadcast_to(k_valid[:, None, :], (b, h_kv, tk))
    k_valid = k_valid.astype(jnp.int32)[:, :, None, :]    # (b, h_kv, 1, tk)
    if pq or pd:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, pd)))
    if pk or pd:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, pd)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, pd)))
    if pk:
        k_valid = jnp.pad(k_valid, ((0, 0), (0, 0), (0, 0), (0, pk)))
    tq_p, tk_p, d_p = tq + pq, tk + pk, d + pd
    n_k = tk_p // block_k
    grid = (b, h, tq_p // block_q, n_k)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, boundary=boundary,
        block_q=block_q, block_k=block_k, n_k=n_k)

    out = pl.pallas_call(
        kernel,
        name="flash_attention_bhtd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_p),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d_p),
                         lambda bi, hi, iq, ik, g=g: (bi, hi // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d_p),
                         lambda bi, hi, iq, ik, g=g: (bi, hi // g, ik, 0)),
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda bi, hi, iq, ik, g=g: (bi, hi // g, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d_p),
                               lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, tq_p, d_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(q, k, v, k_valid)
    return out[:, :, :tq, :d]
