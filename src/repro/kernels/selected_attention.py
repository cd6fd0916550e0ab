"""Gather-free fused selected-attention Pallas kernel.

QUOKA's staged post-selection pipeline pays one full gather round-trip the
selection just saved: ``plan.materialize`` copies every selected KV pair
into a contiguous HBM buffer before ``flash_attention`` ever streams it.
This kernel collapses ``materialize + attention`` into ONE launch with zero
intermediate HBM traffic: the SelectionPlan's grid-granular block ids (and,
on the paged serving path, the pool's block table) arrive as
*scalar-prefetch* operands, and the BlockSpec index maps use them to stream
each selected ``(g, n_kv, d)`` KV slab HBM->VMEM straight from its home
location in the unmaterialized cache.

Mask semantics are exactly ``flash_attention.py``'s
``[selected-prefix | causal-chunk]`` boundary contract, with per-token
validity re-derived IN-KERNEL the same way ``plan.materialize`` re-derives
it (block plans include boundary-straddling blocks whole):

  selected region   attend(i, j)  iff  pos[j] >= 0  and  pos[j] < start
                                  and  the tile's plan id is not -1
  chunk region      attend(i, j)  iff  pos[j] >= 0  and  0 <= j_loc < t
                                  and  j_loc <= i_loc   (chunk-local causal)

so a straddling block contributes its strictly-prior tokens through the
selected region while its suffix attends causally through the chunk region
— never both (the two regions partition on ``pos < start``).

Grid: ``(b, h, ceil(t/block_q), n_sel + n_chunk)`` with the innermost
("arbitrary") dimension carrying the online-softmax scratch (m, l, acc).
The K tile is ``bk = largest divisor of g <= block_k`` so every selected
tile lies inside one grid block; the chunk region walks ``bk``-aligned
cache tiles from ``start`` rounded down (misaligned chunk starts — ragged
harness chunks, decode steps — are handled by the ``j_loc`` bounds).
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


def _softmax_step(ik, s, mask, vb, m_ref, l_ref, acc_ref):
    """One online-softmax accumulation over a (block_q, bk) score tile."""
    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)  # explicit re-mask
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _finalize(ik, n_steps, o_ref, m_ref, l_ref, acc_ref):
    """Divide-out on the last K step; fully-masked rows (l == 0) emit
    zeros, never NaN/Inf (same guard as flash_attention.py)."""
    @pl.when(ik == n_steps - 1)
    def _done():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = jnp.where(
            (l > 0)[:, None], acc_ref[...] / safe[:, None], 0.0
        ).astype(o_ref.dtype)


def _masks(idx_ref, start_ref, pos, *, bi, hi, iq, ik, group, n_sel, r, nb,
           bk, block_q, t):
    """The [selected | chunk] mask for this (iq, ik) tile — the in-kernel
    twin of materialize's validity re-derivation.  ``pos`` is the (bk,)
    absolute key positions of the tile actually streamed in."""
    start = start_ref[bi]
    in_sel = ik < n_sel
    blk = idx_ref[bi, hi // group, jnp.minimum(ik // r, nb - 1)]
    sel_ok = (pos >= 0) & (pos < start) & (blk >= 0)            # (bk,)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
    i_loc = iq * block_q + rows
    j_loc = (ik - n_sel) * bk + lanes - start % bk
    chunk_ok = ((j_loc >= 0) & (j_loc < t) & (j_loc <= i_loc)
                & (pos >= 0)[None, :])
    return jnp.where(in_sel,
                     jnp.broadcast_to(sel_ok[None, :], (block_q, bk)),
                     chunk_ok)


def _kernel(idx_ref, start_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, group, n_sel, r, nb, bk,
            block_q, n_steps, t):
    bi, hi, iq, ik = (pl.program_id(i) for i in range(4))
    qb = q_ref[0, 0].astype(jnp.float32) * scale               # (bq, d)
    kb = k_ref[0, 0].astype(jnp.float32)                       # (bk, d)
    vb = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _masks(idx_ref, start_ref, pos_ref[0], bi=bi, hi=hi, iq=iq,
                  ik=ik, group=group, n_sel=n_sel, r=r, nb=nb, bk=bk,
                  block_q=block_q, t=t)
    _softmax_step(ik, s, mask, vb, m_ref, l_ref, acc_ref)
    _finalize(ik, n_steps, o_ref, m_ref, l_ref, acc_ref)


def _k_tile(bi, hi, ik, idx_ref, start_ref, *, group, n_sel, r, nb, bk,
            n_tiles):
    """Logical cache tile (units of bk tokens) streamed at K step ik.

    Selected region: the plan id drives the tile — plan padding (-1) clamps
    to tile 0 and is masked in-body.  Chunk region: bk-aligned walk from
    ``start`` rounded down; steps past the needed range clamp to the last
    cache tile (their lanes fail the ``j_loc < t`` bound, so the clamped
    DMA is never attended)."""
    blk = jnp.maximum(idx_ref[bi, hi // group, jnp.minimum(ik // r, nb - 1)],
                      0)
    sel_tile = blk * r + ik % r
    chunk_tile = jnp.minimum(start_ref[bi] // bk + (ik - n_sel), n_tiles - 1)
    return jnp.where(ik < n_sel, sel_tile, chunk_tile)


def _resolve_tiles(t, T, d, n_kv, g, nb, block_q, block_k,
                   kernel_name="selected_attention"):
    """Shared geometry resolution: autotune lookup when the caller didn't
    pin tile sizes, then clip to the problem shape."""
    tuned = None
    if block_q is None or block_k is None:
        tuned = autotune.lookup(kernel_name, t=T, d=d, n_kv=n_kv,
                                budget=nb * g, g=g)
        block_q = block_q or tuned["block_q"]
        block_k = block_k or tuned["block_k"]
    block_q = min(block_q, max(8, 1 << (t - 1).bit_length()))
    bk = min(block_k, g)
    while g % bk:                 # largest divisor of g <= block_k
        bk -= 1
    semantics = tuple(tuned["dimension_semantics"]) if tuned else \
        ("parallel", "parallel", "parallel", "arbitrary")
    return block_q, bk, semantics


def _norm_inputs(q, idx, chunk_start, n_kv):
    b = q.shape[0]
    idx = idx.astype(jnp.int32)
    if idx.ndim == 2:             # block plans are shared across KV heads
        idx = jnp.broadcast_to(idx[:, None, :], (b, n_kv, idx.shape[1]))
    start = jnp.asarray(chunk_start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start[None], (b,))
    return idx, start


@functools.partial(
    jax.jit,
    static_argnames=("granularity", "scale", "block_q", "block_k",
                     "interpret"))
def selected_attention_bhtd(q, k, v, key_pos, block_idx, chunk_start, *,
                            granularity: int = 1,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: bool = True):
    """Fused selected attention against a LINEAR cache view.

    q: (b, h, t, d) chunk queries; k, v: (b, n_kv, T, d) unmaterialized
    cache; key_pos: (b, T) absolute positions (-1 = unwritten slot);
    block_idx: ``SelectionPlan.idx`` — (b, B//g) grid block ids at
    granularity g > 1 (shared across KV heads), or (b, n_kv, B) per-head
    token slots at g == 1 (each token is a 1-token block);
    chunk_start: () or (b,) — the chunk's first absolute position, i.e. the
    selected/causal boundary.  Returns (b, h, t, d).
    """
    b, h, t, d = q.shape
    n_kv, T = k.shape[1], k.shape[2]
    group = h // n_kv
    g = int(granularity)
    scale = (d ** -0.5) if scale is None else scale

    idx, start = _norm_inputs(q, block_idx, chunk_start, n_kv)
    nb = idx.shape[2]
    block_q, bk, semantics = _resolve_tiles(
        t, T, d, n_kv, g, nb, block_q, block_k)
    if T % bk:
        raise ValueError(f"cache length {T} must be a multiple of the K "
                         f"tile {bk} (granularity {g})")
    r = g // bk
    n_sel = nb * r
    n_tiles = T // bk
    # chunk walk: enough bk-aligned tiles to cover [start, start + t) for
    # any start alignment (one extra tile absorbs the worst misalignment)
    n_chunk = (t + 2 * bk - 2) // bk
    n_steps = n_sel + n_chunk

    pq = (-t) % block_q
    pd = (-d) % 128 if not interpret else 0
    if pq or pd:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, pd)))
    if pd:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pd)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pd)))
    d_p = d + pd
    grid = (b, h, (t + pq) // block_q, n_steps)

    tile = functools.partial(_k_tile, group=group, n_sel=n_sel, r=r, nb=nb,
                             bk=bk, n_tiles=n_tiles)
    kernel = functools.partial(
        _kernel, scale=scale, group=group, n_sel=n_sel, r=r, nb=nb, bk=bk,
        block_q=block_q, n_steps=n_steps, t=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_p),
                         lambda bi, hi, iq, ik, idx_ref, start_ref:
                         (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, bk, d_p),
                         lambda bi, hi, iq, ik, idx_ref, start_ref:
                         (bi, hi // group,
                          tile(bi, hi, ik, idx_ref, start_ref), 0)),
            pl.BlockSpec((1, 1, bk, d_p),
                         lambda bi, hi, iq, ik, idx_ref, start_ref:
                         (bi, hi // group,
                          tile(bi, hi, ik, idx_ref, start_ref), 0)),
            pl.BlockSpec((1, bk),
                         lambda bi, hi, iq, ik, idx_ref, start_ref:
                         (bi, tile(bi, hi, ik, idx_ref, start_ref))),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d_p),
                               lambda bi, hi, iq, ik, idx_ref, start_ref:
                               (bi, hi, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d_p), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t + pq, d_p), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
    )(idx, start, q, k, v, key_pos.astype(jnp.int32))
    return out[:, :, :t, :d]


# ---------------------------------------------------------------------------
# paged variant: attend THROUGH the pool's block table
# ---------------------------------------------------------------------------

def _paged_kernel(idx_ref, start_ref, table_ref, q_ref, k_ref, v_ref,
                  pos_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, group,
                  n_sel, r, nb, bk, block_q, n_steps, t, tiles_per_block,
                  nb_table):
    bi, hi, iq, ik = (pl.program_id(i) for i in range(4))
    qb = q_ref[0, 0].astype(jnp.float32) * scale
    kb = k_ref[0, :, 0, :].astype(jnp.float32)                 # (bk, d)
    vb = v_ref[0, :, 0, :].astype(jnp.float32)
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _masks(idx_ref, start_ref, pos_ref[0], bi=bi, hi=hi, iq=iq,
                  ik=ik, group=group, n_sel=n_sel, r=r, nb=nb, bk=bk,
                  block_q=block_q, t=t)
    # the tile streamed in came through the block table: unmapped logical
    # blocks (table id -1) clamp to physical block 0 in the index map and
    # must be masked here (a recycled block may hold stale pos >= 0)
    lt = _k_tile(bi, hi, ik, idx_ref, start_ref, group=group, n_sel=n_sel,
                 r=r, nb=nb, bk=bk, n_tiles=nb_table * tiles_per_block)
    mapped = table_ref[bi, jnp.minimum(lt // tiles_per_block,
                                       nb_table - 1)] >= 0
    _softmax_step(ik, s, mask & mapped, vb, m_ref, l_ref, acc_ref)
    _finalize(ik, n_steps, o_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("granularity", "block_size", "scale", "block_q",
                     "block_k", "interpret"))
def selected_attention_paged(q, k_pool, v_pool, pos_pool, block_idx,
                             chunk_start, table, *, granularity: int,
                             block_size: int,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = True):
    """Fused selected attention THROUGH a paged pool's block table — no
    per-request gather of the logical cache at all.

    q: (b, h, t, d); k_pool, v_pool: (N, block_size, n_kv, d) pool leaves
    (physical blocks); pos_pool: (N, block_size); table: (b, nb_logical)
    physical block id per logical block, -1 = unmapped; block_idx /
    chunk_start as in ``selected_attention_bhtd`` but on the LOGICAL grid
    (the logical cache is ``table`` order, length nb_logical * block_size).
    The index maps compose ``physical = table[logical]`` with the plan ids,
    so selected slabs stream straight from their home pool blocks.
    """
    b, h, t, d = q.shape
    n_kv = k_pool.shape[2]
    bs = int(block_size)
    nb_table = table.shape[1]
    T = nb_table * bs
    group = h // n_kv
    g = int(granularity)
    scale = (d ** -0.5) if scale is None else scale
    if bs % g:
        raise ValueError(f"pool block size {bs} must be a multiple of the "
                         f"selection granularity {g}")

    idx, start = _norm_inputs(q, block_idx, chunk_start, n_kv)
    nb = idx.shape[2]
    block_q, bk, semantics = _resolve_tiles(
        t, T, d, n_kv, g, nb, block_q, block_k)
    r = g // bk
    n_sel = nb * r
    tiles_per_block = bs // bk
    n_chunk = (t + 2 * bk - 2) // bk
    n_steps = n_sel + n_chunk

    pq = (-t) % block_q
    pd = (-d) % 128 if not interpret else 0
    if pq or pd:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, pd)))
    if pd:
        k_pool = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, pd)))
        v_pool = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, pd)))
    d_p = d + pd
    grid = (b, h, (t + pq) // block_q, n_steps)

    tile = functools.partial(_k_tile, group=group, n_sel=n_sel, r=r, nb=nb,
                             bk=bk, n_tiles=nb_table * tiles_per_block)

    def _phys(bi, hi, ik, idx_ref, start_ref, table_ref):
        """(physical block, within-block tile) of the logical tile —
        the block-table composition the staged path paid a gather for."""
        lt = tile(bi, hi, ik, idx_ref, start_ref)
        phys = jnp.maximum(
            table_ref[bi, jnp.minimum(lt // tiles_per_block, nb_table - 1)],
            0)
        return phys, lt % tiles_per_block

    def _kv_map(bi, hi, iq, ik, idx_ref, start_ref, table_ref):
        phys, within = _phys(bi, hi, ik, idx_ref, start_ref, table_ref)
        return (phys, within, hi // group, 0)

    def _pos_map(bi, hi, iq, ik, idx_ref, start_ref, table_ref):
        phys, within = _phys(bi, hi, ik, idx_ref, start_ref, table_ref)
        return (phys, within)

    kernel = functools.partial(
        _paged_kernel, scale=scale, group=group, n_sel=n_sel, r=r, nb=nb,
        bk=bk, block_q=block_q, n_steps=n_steps, t=t,
        tiles_per_block=tiles_per_block, nb_table=nb_table)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_p),
                         lambda bi, hi, iq, ik, *refs: (bi, hi, iq, 0)),
            pl.BlockSpec((1, bk, 1, d_p), _kv_map),
            pl.BlockSpec((1, bk, 1, d_p), _kv_map),
            pl.BlockSpec((1, bk), _pos_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d_p),
                               lambda bi, hi, iq, ik, *refs:
                               (bi, hi, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d_p), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t + pq, d_p), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
    )(idx, start, table.astype(jnp.int32), q, k_pool, v_pool,
      pos_pool.astype(jnp.int32))
    return out[:, :, :t, :d]
