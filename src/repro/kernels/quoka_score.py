"""Fused QUOKA scoring Pallas TPU kernel (Algorithm 1 lines 7-10).

The scoring pass is memory-bound: it streams the entire K cache once while
Q̄ (N_Q × d per KV head, a few KB) stays resident in VMEM.  Fusing
(normalise K) -> (Q̄ Kᵀ) -> (max over N_Q) -> (validity mask) means the
(N_Q × T) score matrix never round-trips to HBM — the kernel reads each key
once and writes one fp32 score per key, ~the streaming lower bound.

Grid: (b, n_kv, T/block_t); block working set = block_t × d key tile.
Validated on CPU with interpret=True against kernels/ref.py::quoka_score_ref.

Mosaic tiling: every block's last two dims are multiples of (8, 128) or
equal the array's own, so validity (int32, not bool) and the scores carry
a unit second-minor axis — ``(b, 1, t)`` in ``(1, 1, block_t)`` blocks and
``(b, n_kv, 1, t)`` in ``(1, 1, 1, block_t)`` blocks — and the max over
queries keeps its axis, so no 1-D vector reaches the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qbar_ref, k_ref, valid_ref, o_ref):
    qb = qbar_ref[0, 0].astype(jnp.float32)             # (n_q, d) resident
    kb = k_ref[0, 0].astype(jnp.float32)                # (bt, d) streamed
    inv = jax.lax.rsqrt(jnp.sum(kb * kb, axis=-1, keepdims=True) + 1e-16)
    kn = kb * inv                                       # normalise in-tile
    s = jax.lax.dot_general(qb, kn, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (n_q, bt)
    smax = s.max(axis=0, keepdims=True)                 # (1, bt) over queries
    o_ref[0, 0] = jnp.where(valid_ref[0] != 0, smax, NEG_INF)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def quoka_score_bhtd(qbar, k, valid, *, block_t: int = 512,
                     interpret: bool = True):
    """qbar: (b, n_kv, n_q, d) pre-aggregated normalised queries;
    k: (b, n_kv, t, d) raw keys; valid: (b, t) bool.
    Returns fp32 scores (b, n_kv, t)."""
    b, n_kv, n_q, d = qbar.shape
    t = k.shape[2]
    block_t = min(block_t, max(8, 1 << (t - 1).bit_length()))
    pt = (-t) % block_t
    pd = (-d) % 128 if not interpret else 0
    pq = (-n_q) % 8 if not interpret else 0
    if pt or pd:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pt), (0, pd)))
    valid = valid.astype(jnp.int32)[:, None, :]         # (b, 1, t)
    if pt:
        valid = jnp.pad(valid, ((0, 0), (0, 0), (0, pt)))
    if pd:
        qbar = jnp.pad(qbar, ((0, 0), (0, 0), (0, 0), (0, pd)))  # zeros: dot-safe
    if pq:
        # pad the query axis with COPIES of existing rows — max-invariant (a
        # zero pad would bias the max toward 0 when all real scores are < 0)
        qbar = jnp.pad(qbar, ((0, 0), (0, 0), (0, pq), (0, 0)), mode="edge")
    t_p, d_p, q_p = t + pt, d + pd, n_q + pq
    grid = (b, n_kv, t_p // block_t)

    out = pl.pallas_call(
        _kernel,
        name="quoka_score_bhtd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_p, d_p), lambda bi, hi, it: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_t, d_p),
                         lambda bi, hi, it: (bi, hi, it, 0)),
            pl.BlockSpec((1, 1, block_t), lambda bi, hi, it: (bi, 0, it)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_t),
                               lambda bi, hi, it: (bi, hi, 0, it)),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, 1, t_p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(qbar, k, valid)
    return out[:, :, 0, :t]
