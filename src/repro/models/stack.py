"""Scanned layer stacks.

Homogeneous (or period-repeating) layers are stacked along a leading
`repeats` axis and executed with ``lax.scan`` — one compiled block body per
*period position* regardless of depth, which keeps HLO size and compile time
flat for 40-80 layer models (essential on this 1-core build host, and the
standard production pattern on TPU).

A stack is ``(period, n_repeats)``: e.g. gemma3-27b is
(5×attn_local + 1×attn) × 10 (+ a 2-layer tail stack).  Weight *sharing*
(zamba2's shared attention block) falls out naturally: the shared params are
closed over via ``ctx`` instead of being scanned.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import plan as plan_mod
from repro.models.blocks import make_block
from repro.sharding import ctx as shctx


class Stack:
    def __init__(self, cfg: ModelConfig, period: Sequence[str], repeats: int):
        self.cfg = cfg
        self.period = tuple(period)
        self.repeats = repeats
        self.blocks = [make_block(cfg, k) for k in self.period]

    # ------------------------------------------------------------------
    def init(self, key) -> Tuple:
        """Params: tuple over period positions; leaves have leading
        (repeats, ...) axis."""
        out = []
        for j, blk in enumerate(self.blocks):
            keys = jax.random.split(jax.random.fold_in(key, j), self.repeats)
            ps = [blk.init(k) for k in keys]
            out.append(jax.tree.map(lambda *ls: jnp.stack(ls), *ps))
        return tuple(out)

    def init_cache(self, batch: int, cap: int, dtype) -> Tuple:
        out = []
        for blk in self.blocks:
            spec = blk.cache_spec(batch, cap, dtype)
            out.append(jax.tree.map(
                lambda l: jnp.tile(l[None], (self.repeats,) + (1,) * l.ndim),
                spec))
        return tuple(out)

    # ------------------------------------------------------------------
    def train(self, params: Tuple, x, pos, ctx):
        """Full-sequence forward.  Returns (x, aux_loss)."""
        def body(carry, p_slice):
            h, aux = carry
            for j, blk in enumerate(self.blocks):
                h = shctx.shard_activation(h)
                h, a = blk.train(p_slice[j], h, pos, ctx)
                aux = aux + jnp.asarray(a, jnp.float32)
            return (h, aux), None

        if self.cfg.remat:
            # full recompute.  §Perf B3 measured dots_saveable policy at
            # -2% collectives / -8% flops but +74% peak memory — the wrong
            # trade at 671B scale, where HBM is the binding constraint.
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params)
        return x, aux

    def _plan_carry0(self, caches: Tuple, t: int, ctx, plan):
        """The initial cross-layer plan carry, or None when reuse is off.

        Reuse engages only when ``qcfg.reuse_interval > 1`` AND every period
        position would build the same-shaped plan (uniform geometry): a
        heterogeneous period (mixed capacities, dense-fallback layers, a
        non-selecting block) silently disables the carry and every layer
        builds its own plan — byte-identical to the reuse-off path.  An
        incoming carry from a previous stack is adopted when its shape
        matches, so reuse runs span stack boundaries."""
        qcfg = ctx.get("qcfg") if isinstance(ctx, dict) else None
        method = ctx.get("method", "full") if isinstance(ctx, dict) else "full"
        if (qcfg is None or method == "full"
                or max(1, getattr(qcfg, "reuse_interval", 1)) <= 1):
            return None
        shapes = [getattr(blk, "plan_carry_shape", None) and
                  blk.plan_carry_shape(caches[j], t, method, qcfg)
                  for j, blk in enumerate(self.blocks)]
        if shapes[0] is None or any(s != shapes[0] for s in shapes):
            return None
        if isinstance(plan, plan_mod.PlanCarry) and plan.idx.shape == shapes[0]:
            return plan
        return plan_mod.empty_carry(shapes[0])

    def apply(self, params: Tuple, x, pos, caches: Tuple, ctx, plan=None):
        """Prefill-chunk / decode forward with caches.
        Returns (x, new_caches, aux, plan, obs, sel) — ``plan`` is the
        cross-layer ``PlanCarry`` threaded through the scan when
        KV-selection reuse is on (core/plan.py), passed through untouched
        otherwise.  ``obs`` is a ``LayerObs`` pytree with (n_layers,)
        leaves in global layer order when ``ctx["obs"]`` is set, else None:
        each block leaves its per-layer stats in its ctx copy (the MoE
        aux-loss side-channel) and the scan body collects them as ys —
        seven scalars per layer, nothing like the cache-ys trap below.
        ``sel`` is the prefetch-oracle side channel: when
        ``ctx["selblk"] = (block_size, n_blocks)`` is set, the (b,
        n_blocks) int32 sum over this stack's layers of each plan's
        ``pool_block_counts`` (layers that left none — dense, recurrent —
        count zero), else None.

        Caches live in the scan CARRY and are updated through WINDOWED
        dynamic-update-slices (only the rows a chunk actually writes), not
        as scan ys.  The ys formulation shuttles every layer's full cache
        through the loop boundary per chunk — measured at 47 TB/chip for a
        32k prefill (§Perf A3) — while XLA aliases a loop-carried buffer in
        place, so this path books only the written rows.
        """
        t = x.shape[1]
        slot = ctx.get("slot")
        start = pos[0, 0] if slot is None else slot
        carry0 = self._plan_carry0(caches, t, ctx, plan)
        layer0 = int(ctx.get("layer0", 0)) if isinstance(ctx, dict) else 0
        n_period = len(self.blocks)
        obs_on = isinstance(ctx, dict) and bool(ctx.get("obs"))
        sel_on = isinstance(ctx, dict) and ctx.get("selblk") is not None

        def write_back(blk, buf_tree, new_slice, idx):
            """Windowed write of one layer's cache updates into the stacked
            buffers.  KV/latent rows: only the [start, start+t) window (mod W
            for ring buffers); recurrent states: whole (small) leaves.  XLA
            simplifies slice(DUS(orig, rows)) back to the rows, so the
            block's full returned cache never materialises."""
            s32 = jnp.asarray(start, jnp.int32)

            def upd_rows(buf, new, ring: bool):
                # buf: (R, b, cap, ...); new: (b, cap, ...)
                if t == 1 and new.shape[0] == 1:
                    # batch-1 decode: the cache shards its SEQUENCE axis
                    # (sharding/specs.py), and a windowed DUS at a traced
                    # position into a sequence-sharded buffer makes GSPMD
                    # reshard (measured +240 ms collective on zamba2
                    # long_500k); a whole-slice write keeps layouts aligned.
                    # Batched decode caches shard over BATCH instead — the
                    # windowed write below stays collective-free there.
                    return buf.at[idx].set(new.astype(buf.dtype))
                if s32.ndim == 1:
                    # per-request write offsets (continuous batching): every
                    # step-batch row writes its own [slot, slot + t) window
                    rows = jax.vmap(lambda n, s: jax.lax.dynamic_slice_in_dim(
                        n, s, t, axis=0))(new, s32)          # (b, t, ...)
                    slots = s32[:, None] + jnp.arange(t, dtype=jnp.int32)
                    bidx = jnp.arange(new.shape[0])[:, None]
                    return buf.at[idx, bidx, slots].set(rows.astype(buf.dtype))
                if ring:
                    cap = buf.shape[2]
                    slots = (s32 + jnp.arange(t, dtype=jnp.int32)) % cap
                    rows = jnp.take(new, slots, axis=1)      # (b, t, ...)
                    # two advanced indices (traced idx + slots) move the
                    # indexed axes to the front: update shape is (t, b, ...)
                    return buf.at[idx, :, slots].set(rows.swapaxes(0, 1))
                rows = jax.lax.dynamic_slice_in_dim(new, s32, t, axis=1)
                starts = (idx, jnp.zeros((), jnp.int32), s32) + \
                    tuple(jnp.zeros((), jnp.int32) for _ in range(buf.ndim - 3))
                return jax.lax.dynamic_update_slice(
                    buf, rows[None].astype(buf.dtype), starts)

            out = []
            for name in buf_tree._fields:
                b_f = getattr(buf_tree, name)
                n_f = getattr(new_slice, name)
                if b_f == () or b_f is None:
                    out.append(b_f)
                    continue
                if name in ("kv", "latent"):
                    ring = (name == "kv"
                            and getattr(blk, "window", None) is not None)
                    out.append(type(b_f)(**{
                        ln: upd_rows(getattr(b_f, ln), getattr(n_f, ln), ring)
                        for ln in b_f._fields}))
                else:   # mamba / rwkv / cross: small states, copy whole
                    out.append(jax.tree.map(
                        lambda lb, nn: lb.at[idx].set(nn), b_f, n_f))
            return type(buf_tree)(*out)

        def body(carry, xs):
            if carry0 is not None:
                h, aux, bufs, pc = carry
            else:
                h, aux, bufs = carry
                pc = None
            p_slice, idx = xs
            new_bufs = []
            obs_j, sel_j = [], []
            for j, blk in enumerate(self.blocks):
                h = shctx.shard_activation(h)
                c_slice = jax.tree.map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l, idx, axis=0, keepdims=False), bufs[j])
                # obs/sel need a PER-LAYER ctx copy (each layer pops its own
                # "_obs"/"_selblk"); the reuse carry needs one for layer_idx
                cj = ctx if pc is None and not obs_on and not sel_on else \
                    dict(ctx, layer_idx=layer0 + idx * n_period + j)
                h, c_new, a, pc = blk.apply(p_slice[j], h, pos, c_slice, cj,
                                            plan=pc)
                if obs_on:
                    ob = cj.pop("_obs", None)
                    obs_j.append(plan_mod.nan_obs() if ob is None else ob)
                if sel_on:
                    sb = cj.pop("_selblk", None)
                    sel_j.append(jnp.zeros((h.shape[0], ctx["selblk"][1]),
                                           jnp.int32) if sb is None else sb)
                with jax.named_scope("kv_write"):
                    new_bufs.append(write_back(blk, bufs[j], c_new, idx))
                aux = aux + jnp.asarray(a, jnp.float32)
            out = (h, aux, tuple(new_bufs))
            ys = (jax.tree.map(lambda *ls: jnp.stack(ls), *obs_j)
                  if obs_on else None,
                  jnp.stack(sel_j) if sel_on else None)
            return (out + (pc,) if carry0 is not None else out), ys

        idxs = jnp.arange(self.repeats, dtype=jnp.int32)
        init = (x, jnp.zeros((), jnp.float32), caches)
        if carry0 is not None:
            init = init + (carry0,)
        # names the scan's own work: each layer's weights and cache view
        # sliced out of the stacked buffers.  An op in the body with no
        # scope of its own inherits it too, so a reader of the op metadata
        # counts it as this scope's only on those slices (bench/scopes.py)
        with jax.named_scope("stack"):
            out, ys = jax.lax.scan(body, init, (params, idxs))
        if carry0 is not None:
            x, aux, caches, plan = out
        else:
            x, aux, caches = out
        obs_ys, sel_ys = ys
        obs = None
        if obs_on:
            # ys leaves: (repeats, n_period) -> flatten to global layer
            # order within this stack (layer = idx * n_period + j)
            obs = jax.tree.map(lambda l: l.reshape(-1, *l.shape[2:]), obs_ys)
        # (repeats, n_period, b, n_blocks) -> stack total per pool block
        sel = sel_ys.sum(axis=(0, 1)) if sel_on else None
        return x, caches, aux, plan, obs, sel
