"""Unified model: embeddings + scanned block stacks + read-out, with
three entry points used across the framework:

  * ``train_logits`` / ``loss``     — full-sequence training forward
  * ``prefill``                     — CHUNKED prefill (paper Algorithm 2):
                                      a lax.scan over chunks; each chunk
                                      sub-selects the KV cache per layer
  * ``decode_step``                 — one-token decode with selection

Modality frontends (VLM patches / whisper frames) are stubs per the
assignment: the batch provides pre-computed embeddings; the in-model
projector / encoder transformer consumes them.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import DecCrossBlock, MLABlock, make_block
from repro.models.layers import (embed, embed_init, linear, linear_init,
                                 mlp_init, rmsnorm, rmsnorm_init, sinusoidal,
                                 unembed)
from repro.models.stack import Stack


class ModelCache(NamedTuple):
    stacks: Tuple            # tuple over stacks of tuple-over-positions
    enc_done: jax.Array      # () bool — whisper encoder ran (unused otherwise)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.stacks = [Stack(cfg, period, reps)
                       for period, reps in cfg.stacks()]
        self.has_shared = any(k == "mamba_shared_attn"
                              for pd, _ in cfg.stacks() for k in pd)
        self.is_audio = cfg.family == "audio"
        self.is_vlm = cfg.family == "vlm"
        if self.is_audio:
            self.enc_stack = Stack(cfg, ("enc_attn",), cfg.encoder.n_layers)

    # ------------------------------------------------------------------
    def init(self, key, dtype=None) -> Dict:
        """Parameter pytree.  Leaves are fp32 (training's master weights)
        unless ``dtype`` is given: serving passes ``cfg.compute_dtype``,
        and the cast runs inside the same jitted program as the random
        init, so no fp32 copy of the model is ever resident (qwen3-4b is
        15 GiB in fp32 against a 16 GiB chip, 7.5 GiB in bf16)."""
        if dtype is not None:
            return jax.jit(lambda k: jax.tree.map(
                lambda a: a.astype(dtype), self.init(k)))(key)
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p = {
            "embed": embed_init(ks[0], cfg.vocab, cfg.d_model),
            "stacks": tuple(s.init(jax.random.fold_in(ks[1], i))
                            for i, s in enumerate(self.stacks)),
            "ln_f": rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = linear_init(ks[2], cfg.d_model, cfg.vocab)
        if self.has_shared:
            shared_blk = make_block(cfg, "attn")
            p["shared"] = shared_blk.init(ks[3])
        if self.is_audio:
            p["enc"] = {"stack": self.enc_stack.init(ks[4]),
                        "ln": rmsnorm_init(cfg.d_model)}
        if self.is_vlm:
            f = cfg.frontend
            p["proj"] = {"fc1": linear_init(ks[5], f.d_in, cfg.d_model,
                                            bias=True),
                         "fc2": linear_init(ks[6], cfg.d_model, cfg.d_model,
                                            bias=True)}
        if cfg.mtp:
            mtp_blk = MLABlock(cfg, "mla") if cfg.mla else make_block(cfg, "attn")
            p["mtp"] = {"block": mtp_blk.init(ks[7]),
                        "ln": rmsnorm_init(cfg.d_model),
                        "mix": linear_init(jax.random.fold_in(ks[7], 1),
                                           2 * cfg.d_model, cfg.d_model)}
        return p

    # ------------------------------------------------------------------
    # input embedding (modality frontends are stubs — see module docstring)
    # ------------------------------------------------------------------
    def embed_inputs(self, p, batch: Dict) -> Tuple[jax.Array, jax.Array]:
        """Returns (x (b, T, d), pos (b, T)).

        ``batch["pad"]`` (b,) optionally gives per-row LEFT-pad counts: pad
        slots get ``pos = -1`` (invalid), masking them out of attention, KV
        selection scoring and the cache — pad tokens are NOT ordinary
        context.  (Recurrent blocks still see pad embeddings sequentially;
        exact pad masking holds for attention-cache architectures.)"""
        cfg = self.cfg
        dt = cfg.compute_dtype
        tok = batch["tokens"]
        x = embed(p["embed"], tok, dt)
        if self.is_vlm:
            if batch.get("pad") is not None:
                raise ValueError("left-padding unsupported for VLM inputs")
            pe = batch["patches"].astype(dt)              # (b, n_patch, d_in)
            h = jax.nn.gelu(linear(p["proj"]["fc1"], pe))
            h = linear(p["proj"]["fc2"], h)
            x = jnp.concatenate([h, x], axis=1)
        b, t = x.shape[:2]
        pos = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
        pad = batch.get("pad")
        if pad is not None:
            pad = jnp.asarray(pad, jnp.int32)
            pos = jnp.where(jnp.arange(t, dtype=jnp.int32)[None] < pad[:, None],
                            -1, pos)
        if not cfg.use_rope:
            x = x + sinusoidal(pos, cfg.d_model, dt)
        from repro.sharding import ctx as shctx
        return shctx.shard_activation(x), pos

    def encode(self, p, frames) -> jax.Array:
        """Whisper encoder over stub frame embeddings (b, n_ctx, d)."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        b, s, _ = frames.shape
        pos = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, 0)
        x = frames.astype(dt) + sinusoidal(pos, cfg.d_model, dt)
        x, _ = self.enc_stack.train(p["enc"]["stack"], x, pos, {})
        return rmsnorm(p["enc"]["ln"], x, cfg.norm_eps)

    def _ctx(self, p, method: str, enc_out=None,
             backend: Optional[str] = None) -> Dict:
        import dataclasses

        from repro.kernels import ops as kops
        # kernel backend resolved ONCE at trace time (env/config/hardware)
        # and baked into the qcfg handed to every layer, so the scoring
        # stage (sel_mod.select -> quoka_scores) dispatches consistently
        # with the attention stage
        be = kops.resolve_backend(backend, self.cfg.quoka)
        ctx = {"method": method,
               "qcfg": dataclasses.replace(self.cfg.quoka, backend=be),
               "backend": be}
        if self.has_shared:
            ctx["shared"] = p["shared"]
        if enc_out is not None:
            ctx["enc_out"] = enc_out
        return ctx

    def _readout(self, p, x) -> jax.Array:
        with jax.named_scope("readout"):
            x = rmsnorm(p["ln_f"], x, self.cfg.norm_eps)
            if self.cfg.tie_embeddings:
                return unembed(p["embed"], x)
            return linear(p["lm_head"], x.astype(jnp.float32))

    # ------------------------------------------------------------------
    # training forward
    # ------------------------------------------------------------------
    def train_logits(self, p, batch: Dict) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence logits.  Returns (logits (b,T,V), aux_loss)."""
        enc_out = self.encode(p, batch["frames"]) if self.is_audio else None
        x, pos = self.embed_inputs(p, batch)
        ctx = self._ctx(p, "full", enc_out)
        aux = jnp.zeros((), jnp.float32)
        for s, sp in zip(self.stacks, p["stacks"]):
            x, a = s.train(sp, x, pos, ctx)
            aux = aux + a
        hidden = x
        logits = self._readout(p, x)
        if self.cfg.mtp:
            aux = aux + self._mtp_loss(p, hidden, pos, batch, ctx)
        return logits, aux

    def _mtp_loss(self, p, hidden, pos, batch, ctx) -> jax.Array:
        """DeepSeek-V3 multi-token prediction: one extra block predicts
        token t+2 from [norm(h_t); emb(tok_{t+1})] (weight 0.3)."""
        cfg = self.cfg
        tok = batch["tokens"]
        nxt = jnp.roll(tok, -1, axis=1)
        emb_n = embed(p["embed"], nxt, hidden.dtype)
        h = rmsnorm(p["mtp"]["ln"], hidden, cfg.norm_eps)
        h = linear(p["mtp"]["mix"], jnp.concatenate([h, emb_n], axis=-1))
        blk = MLABlock(cfg, "mla") if cfg.mla else make_block(cfg, "attn")
        h, _ = blk.train(p["mtp"]["block"], h, pos, ctx)
        logits = self._readout(p, h)                    # predicts t+2
        tgt = jnp.roll(tok, -2, axis=1)
        mask = jnp.arange(tok.shape[1]) < tok.shape[1] - 2
        ll = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(ll, tgt[..., None], axis=-1)[..., 0]
        return 0.3 * jnp.mean(nll * mask[None, :])

    def loss(self, p, batch: Dict) -> jax.Array:
        """Next-token cross entropy (+ MoE/MTP aux).  For VLM the frontend
        positions are excluded; for whisper the loss is over decoder tokens."""
        logits, aux = self.train_logits(p, batch)
        tok = batch["tokens"]
        if self.is_vlm:                                  # drop patch positions
            logits = logits[:, -tok.shape[1]:]
        tgt = tok[:, 1:]
        lg = logits[:, :-1]
        ll = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(ll, tgt[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            return (nll * m).sum() / jnp.maximum(m.sum(), 1.0) + aux
        return nll.mean() + aux

    # ------------------------------------------------------------------
    # serving: chunked prefill (Algorithm 2) + decode
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, cap: int) -> ModelCache:
        dt = self.cfg.compute_dtype
        return ModelCache(
            stacks=tuple(s.init_cache(batch, cap, dt) for s in self.stacks),
            enc_done=jnp.zeros((), bool),
        )

    def _apply_stacks(self, p, x, pos, cache: ModelCache, ctx):
        """Returns (x, cache, aux, obs, sel): ``obs`` is the per-layer
        LayerObs aux-stats pytree with (n_layers,) leaves in GLOBAL layer
        order when ``ctx["obs"]`` is set (core/plan.py), else None; ``sel``
        is the (b, n_blocks) int32 selection-count total over all layers
        when ``ctx["selblk"]`` is set (the prefetch oracle), else None."""
        new = []
        aux = jnp.zeros((), jnp.float32)
        plan = None           # cross-layer SelectionPlan carry (core/plan.py)
        layer0 = 0            # global layer offset for the reuse schedule
        obs = [] if ctx.get("obs") else None
        sel = None
        for s, sp, sc in zip(self.stacks, p["stacks"], cache.stacks):
            x, nc, a, plan, ob, sb = s.apply(
                sp, x, pos, sc, dict(ctx, layer0=layer0), plan=plan)
            layer0 += len(s.period) * s.repeats
            new.append(nc)
            aux = aux + a
            if obs is not None:
                obs.append(ob)
            if sb is not None:
                sel = sb if sel is None else sel + sb
        if obs is not None:
            obs = obs[0] if len(obs) == 1 else \
                jax.tree.map(lambda *ls: jnp.concatenate(ls), *obs)
        return x, cache._replace(stacks=tuple(new)), aux, obs, sel

    def _build_cross(self, p, cache: ModelCache, enc_out) -> ModelCache:
        """Fill whisper cross-attention KV (vmapped over stacked layers)."""
        blk: DecCrossBlock = self.stacks[0].blocks[0]
        new_stacks = []
        for s, sp, sc in zip(self.stacks, p["stacks"], cache.stacks):
            pos_caches = []
            for j, b in enumerate(s.blocks):
                c = sc[j]
                if b.kind == "dec_cross":
                    cross = jax.vmap(b.build_cross, in_axes=(0, None))(
                        sp[j], enc_out)
                    c = c._replace(cross=jax.tree.map(
                        lambda l: l.astype(self.cfg.compute_dtype), cross))
                pos_caches.append(c)
            new_stacks.append(tuple(pos_caches))
        return cache._replace(stacks=tuple(new_stacks),
                              enc_done=jnp.ones((), bool))

    def prefill(self, p, batch: Dict, cache: ModelCache,
                method: Optional[str] = None,
                backend: Optional[str] = None
                ) -> Tuple[jax.Array, ModelCache]:
        """Chunked prefill of the full prompt.  Returns (last-position
        logits (b, V), filled cache)."""
        cfg = self.cfg
        method = method or cfg.quoka.method
        if self.is_audio:
            enc_out = self.encode(p, batch["frames"])
            cache = self._build_cross(p, cache, enc_out)
        x_all, pos_all = self.embed_inputs(p, batch)
        b, t, d = x_all.shape
        bcp = min(cfg.quoka.chunk_size, t)
        assert t % bcp == 0, f"prompt length {t} must be a multiple of {bcp}"
        nc = t // bcp
        xs = x_all.reshape(b, nc, bcp, d).swapaxes(0, 1)
        ps = pos_all.reshape(b, nc, bcp).swapaxes(0, 1)
        # write SLOT of each chunk — distinct from pos: pad slots carry
        # pos == -1 but still occupy their cache slot
        slots = jnp.arange(nc, dtype=jnp.int32) * bcp
        ctx = self._ctx(p, method, backend=backend)

        def body(carry, inp):
            cch, _ = carry
            xc, pc, sl = inp
            h, cch, _aux, _, _ = self._apply_stacks(p, xc, pc, cch,
                                                    dict(ctx, slot=sl))
            return (cch, h[:, -1, :]), None

        (cache, last_h), _ = jax.lax.scan(
            body, (cache, jnp.zeros((b, d), cfg.compute_dtype)),
            (xs, ps, slots))
        return self._readout(p, last_h[:, None, :])[:, 0], cache

    def prefill_chunk(self, p, batch: Dict, pos_start, cache: ModelCache,
                      method: Optional[str] = None,
                      backend: Optional[str] = None,
                      valid_len=None, with_obs: bool = False,
                      sel_blocks: Optional[Tuple[int, int]] = None):
        """One B_CP chunk through all stacks — the steady-state unit of
        chunked prefill for per-chunk dispatch (continuous batching / the
        production serving path; §Perf: carrying caches through a scan over
        chunks shuttles every layer's full cache per chunk, while per-chunk
        dispatch with a DONATED cache updates 128 rows in place).

        batch["tokens"]: (b, B_CP) chunk; pos_start: traced scalar, or a
        per-row (b,) vector under continuous batching (each request's chunk
        starts at its own offset).  ``valid_len`` (b,) optionally marks how
        many leading chunk tokens are real (tail chunks of a ragged batch;
        the rest get pos = -1 and are masked everywhere).
        Returns (last VALID hidden (b, d), cache); with ``with_obs=True``
        additionally returns the per-layer ``LayerObs`` aux-stats pytree
        (leaves (n_layers,)) as a third output — extra jit outputs, no host
        callbacks (the selection computation itself is unchanged).
        ``sel_blocks = (block_size, n_blocks)`` appends the prefetch-oracle
        selection-count output ((b, n_blocks) int32, summed over layers)
        after ``obs`` (same extra-jit-output pattern; orthogonal flags)."""
        cfg = self.cfg
        method = method or cfg.quoka.method
        tok = batch["tokens"]
        b, t = tok.shape
        dt = cfg.compute_dtype
        with jax.named_scope("embed"):
            x = embed(p["embed"], tok, dt)
            s = jnp.asarray(pos_start, jnp.int32)
            offs = jnp.arange(t, dtype=jnp.int32)
            pos = (s + offs)[None].repeat(b, 0) if s.ndim == 0 \
                else s[:, None] + offs[None]
            if valid_len is not None:
                vl = jnp.asarray(valid_len, jnp.int32)
                pos = jnp.where(offs[None] < vl[:, None], pos, -1)
            if not cfg.use_rope:
                x = x + sinusoidal(pos, cfg.d_model, dt)
        from repro.sharding import ctx as shctx
        x = shctx.shard_activation(x)
        ctx = self._ctx(p, method, backend=backend)
        ctx["slot"] = s
        if with_obs:
            ctx["obs"] = True
        if sel_blocks is not None:
            ctx["selblk"] = (int(sel_blocks[0]), int(sel_blocks[1]))
        x, cache, _, obs, sel = self._apply_stacks(p, x, pos, cache, ctx)
        with jax.named_scope("readout"):
            if valid_len is None:
                last = x[:, -1, :]
            else:
                li = jnp.clip(vl - 1, 0, t - 1)
                last = jnp.take_along_axis(x, li[:, None, None],
                                           axis=1)[:, 0, :]
        out = (last, cache)
        if with_obs:
            out = out + (obs,)
        if sel_blocks is not None:
            out = out + (sel,)
        return out if len(out) > 2 else (last, cache)

    def decode_step(self, p, tokens, pos, cache: ModelCache,
                    method: Optional[str] = None,
                    backend: Optional[str] = None,
                    with_obs: bool = False,
                    sel_blocks: Optional[Tuple[int, int]] = None):
        """One decode step.  tokens: (b,) int32; pos: scalar or (b,)
        (per-request positions under continuous batching).
        Returns (logits (b, V), cache), plus the per-layer ``LayerObs``
        pytree when ``with_obs=True`` and the (b, n_blocks) selection-count
        output when ``sel_blocks`` is set (see prefill_chunk; obs first)."""
        cfg = self.cfg
        method = method or cfg.quoka.method
        dt = cfg.compute_dtype
        b = tokens.shape[0]
        with jax.named_scope("embed"):
            x = embed(p["embed"], tokens[:, None], dt)
            ps = jnp.asarray(pos, jnp.int32)
            pos2 = jnp.broadcast_to(ps.reshape(-1, 1), (b, 1))
            if not cfg.use_rope:
                x = x + sinusoidal(pos2, cfg.d_model, dt)
        ctx = self._ctx(p, method, backend=backend)
        ctx["slot"] = ps
        if with_obs:
            ctx["obs"] = True
        if sel_blocks is not None:
            ctx["selblk"] = (int(sel_blocks[0]), int(sel_blocks[1]))
        x, cache, _, obs, sel = self._apply_stacks(p, x, pos2, cache, ctx)
        logits = self._readout(p, x)[:, 0]
        out = (logits, cache)
        if with_obs:
            out = out + (obs,)
        if sel_blocks is not None:
            out = out + (sel,)
        return out if len(out) > 2 else (logits, cache)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
