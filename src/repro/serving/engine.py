"""Serving engine: batched chunked prefill (QUOKA Algorithm 2) + decode.

Two serving modes share the model and kernel facade:

  * ``generate`` — one synchronous batch: a jitted scan-prefill followed by
    a Python decode loop (TTFT / decode-throughput probe, paper §4.6).
    Tokens accumulate ON DEVICE; the single host sync happens after the
    loop, so ``decode_tps`` measures compute, not transfers.
  * ``step``/``serve`` — continuous batching: a paged KV pool
    (serving/pool.py) plus a request-lifecycle scheduler
    (serving/scheduler.py) drive two jitted step functions — a mixed
    chunk-prefill step and a batched decode step — that gather each
    request's blocks via its block table into a linear cache view, run the
    existing model/kernel path, and scatter the touched blocks back.
    Prefill chunks of new requests interleave with decode steps of running
    ones (Sarathi-style), which is what chunked prefill exists for.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.obs import registry as obs_reg
from repro.serving.sampler import SamplerConfig, sample


@dataclass
class GenerationResult:
    tokens: np.ndarray            # (b, max_new)
    ttft_s: float                 # time to first token (prefill + 1 sample)
    decode_tps: float             # decoded tokens/sec across the batch
    prompt_len: int               # TRUE prompt length (pad_prompt padding
                                  # excluded — per-token TTFT normalisation)
    method: str
    backend: str = "auto"         # resolved kernel backend of this run
    logits: Optional[jax.Array] = None   # (b, V) fp32 first-token logits


@dataclass
class ServeState:
    """Mutable state of one continuous-batching run (pool + scheduler +
    compiled step functions + PRNG + counters)."""
    pool: object
    sched: object
    fns: Tuple
    key: object
    chunk: int
    max_nb: int
    b_prefill: int
    b_decode: int
    host_tier: int = 0            # host-tier capacity (0 = single level)
    prefetch_depth: int = 0       # max H2D stages dispatched per step
    hot: Optional[np.ndarray] = None   # (max_nb,) decayed selection counts
    host_ctr: Tuple = (0, 0, 0, 0)     # last pool tier counters seen
    t0: float = field(default_factory=time.perf_counter)
    steps: int = 0
    prefill_steps: int = 0
    decode_steps: int = 0
    occupancy: List[float] = field(default_factory=list)
    #: (rid, token) pairs emitted by the LAST step() — the streaming feed
    events: List[Tuple[int, int]] = field(default_factory=list)

    @property
    def now(self) -> float:
        return time.perf_counter() - self.t0


@dataclass
class ServeResult:
    """Outcome of one continuous-batching trace."""
    tokens: Dict[int, np.ndarray]        # rid -> (n_generated,) int32
    ttft_s: Dict[int, float]             # rid -> time to first token
    latency_s: Dict[int, float]          # rid -> arrival -> completion
    wall_s: float
    generated: int                       # total tokens across requests
    tokens_per_s: float                  # generated / wall_s
    steps: int = 0
    prefill_steps: int = 0
    decode_steps: int = 0
    occupancy: float = 0.0               # mean decode-batch fill [0, 1]
    method: str = ""
    backend: str = ""
    cached_len: Dict[int, int] = field(default_factory=dict)  # rid -> prefix hit
    prefix: Dict[str, float] = field(default_factory=dict)    # cache stats
    policy: str = ""                     # scheduling policy name
    preemptions: int = 0                 # suspends during this trace
    resumes: int = 0                     # re-admissions of suspended requests
    deadline_misses: int = 0             # TTFT deadlines blown this trace


class Engine:
    def __init__(self, model: Model, params, *, method: Optional[str] = None,
                 backend: Optional[str] = None,
                 sampler: SamplerConfig = SamplerConfig(),
                 mesh=None, registry=None):
        """``backend`` overrides the kernel backend for this engine
        ("xla" | "pallas_interpret" | "pallas"); None defers to the env /
        ``QuokaConfig.backend`` / hardware resolution (kernels/ops.py).

        ``mesh`` (jax.sharding.Mesh with axes from (pod, data, model), see
        launch/mesh.py) turns on tensor-/data-parallel serving: params are
        placed via ``sharding/specs.param_specs``, caches (one-shot AND the
        paged pool) via ``cache_specs``, the jitted step functions are
        donated + constrained with NamedSharding in/out specs, and QUOKA
        scoring routes through the T-local shard_map path when the KV-head
        axis under-shards the `model` axis (core/quoka.py).  Greedy outputs
        are token-identical to the meshless engine
        (tests/test_sharded_serving.py).

        ``registry`` (repro.obs.Registry) turns on serve-path telemetry:
        step spans, scheduler/pool counters, and the in-jit per-layer
        selection stats (the step functions compile WITH the LayerObs
        aux outputs — extra jit outputs, no host callbacks; with no
        registry they compile without them, so the metrics-off compute is
        bit-identical to pre-telemetry behavior).  ``Engine.stats`` is a
        view of this registry either way (an ephemeral one when off)."""
        from repro.kernels import ops as kops
        self.model = model
        self.mesh = mesh
        self.method = method or model.cfg.quoka.method
        self.backend = kops.resolve_backend(backend, model.cfg.quoka)
        # gather-free serve path: with QuokaConfig.fused_select_attn on and
        # a block-granular grid, every selecting layer inside the jitted
        # step functions routes through kernels/selected_attention.py
        # (core/plan.py::fused_route — the flag rides in via ctx["qcfg"],
        # no step-function change needed).  The paged gather that builds
        # the per-request cache VIEW remains (scatter-back needs it); what
        # the fused path removes is the per-layer full-budget materialize.
        # Benchmarks stamp this onto their records as the `fused` axis.
        self.fused = bool(getattr(model.cfg.quoka, "fused_select_attn",
                                  False))
        if self.fused and self.backend == "pallas":
            raise ValueError(
                "fused_select_attn=True does not compile on the 'pallas' "
                "backend: kernels/selected_attention.py streams K in tiles "
                "of at most `granularity` rows with (1, tile) key-position "
                "blocks, and Mosaic requires a block's last two dims to be "
                "multiples of (8, 128) or equal to the array's own.  Serve "
                "with fused_select_attn=False (the staged path), or use "
                "backend 'xla' / 'pallas_interpret' for parity runs")
        self.sampler = sampler
        self.registry = registry if registry is not None else obs_reg.NULL
        self._obs_on = bool(self.registry.enabled)
        self.stats: Dict[str, float] = {}   # prefix-cache stats of last serve
        self._warmed: set = set()           # generate() jit-warmup signatures
        donate = {}
        if mesh is not None:
            from repro.sharding import specs as sh
            self._param_sh = sh.to_shardings(
                mesh, sh.param_specs(model.cfg, params, mesh))
            params = jax.device_put(params, self._param_sh)
            # donate the cache so XLA updates the sharded buffers in place
            donate = dict(donate_argnums=(2,))
        self.params = params
        self._prefill = jax.jit(
            lambda p, batch, cache: model.prefill(p, batch, cache,
                                                  self.method,
                                                  backend=self.backend),
            **donate)
        self._decode = jax.jit(
            lambda p, tok, pos, cache: model.decode_step(p, tok, pos, cache,
                                                         self.method,
                                                         backend=self.backend),
            **(dict(donate_argnums=(3,)) if mesh is not None else {}))
        self._cont_fns: Dict = {}

    def _call(self, fn, *args):
        """Invoke a jitted step.  Under a mesh the sharding policy
        (sharding/ctx.py) and mesh context are active for the duration —
        they only matter at trace time (with_sharding_constraint + the
        quoka shard_map route), and save/restore keeps an outer launcher's
        policy intact."""
        if self.mesh is None:
            return fn(*args)
        from repro.sharding import ctx as shctx
        snap = shctx.get_policy()
        shctx.set_policy(self.mesh, tuple(a for a in ("pod", "data")
                                          if a in self.mesh.axis_names))
        try:
            with self.mesh:
                return fn(*args)
        finally:
            shctx.restore_policy(snap)

    # ------------------------------------------------------------------
    # one-shot batch mode
    # ------------------------------------------------------------------
    def pad_prompt(self, tokens: np.ndarray) -> Dict[str, np.ndarray]:
        """Left-pad to a chunk multiple.  Returns a batch dict whose
        ``pad`` entry carries the per-row pad count: inside the model, pad
        slots get ``pos = -1`` and are masked out of attention AND KV
        selection scoring — they are NOT ordinary context and cannot skew
        QUOKA's mean-query/key statistics.  (Recurrent blocks still consume
        pad embeddings sequentially; masking is exact for attention-cache
        architectures.)"""
        tokens = np.asarray(tokens)
        bcp = self.model.cfg.quoka.chunk_size
        t = tokens.shape[1]
        pad = (-t) % bcp
        if pad:
            tokens = np.concatenate(
                [np.zeros((tokens.shape[0], pad), tokens.dtype), tokens], 1)
        return {"tokens": tokens,
                "pad": np.full((tokens.shape[0],), pad, np.int32)}

    def generate(self, batch: Dict, max_new: int, *,
                 key=None) -> GenerationResult:
        """batch['tokens']: (b, T) prompt (T % chunk_size == 0; use
        pad_prompt, whose 'pad' entry rides along).  Extra modality inputs
        pass through."""
        model, params = self.model, self.params
        tokens = np.asarray(batch["tokens"])
        b, t = tokens.shape
        extra = t + (model.cfg.frontend.n_tokens
                     if model.cfg.family == "vlm" else 0)
        cap = extra + max_new
        g = model.cfg.quoka.granularity
        if self.method != "full" and g > 1:
            # block-granular plans need the cache view on the selection
            # grid (core/plan.py); padding slots read pos = -1 and their
            # blocks score NEG_INF, so rounding up is free
            cap = -(-cap // g) * g
        cache = model.init_cache(b, cap)
        if self.mesh is not None:
            from repro.sharding import specs as sh
            cache = jax.device_put(cache, sh.to_shardings(
                self.mesh, sh.cache_specs(model.cfg, cache, self.mesh)))
            batch = jax.device_put(batch, sh.to_shardings(
                self.mesh, sh.batch_spec(model.cfg, batch, self.mesh)))
        key = key if key is not None else jax.random.PRNGKey(0)

        # exclude jit compile time from the clocks: the first call on a new
        # (shapes, dtypes) signature traces + compiles inside the timed
        # region, so a cold first generate() used to report compile-dominated
        # ttft_s.  Warm the jit caches on a THROWAWAY cache with identical
        # avals (the real cache may be donated under a mesh), then time
        # execution only.  Repeat calls hit the signature set and skip this.
        sig = (b, t, cap, max_new > 1,
               tuple(sorted(k for k in batch if batch[k] is not None)))
        if sig not in self._warmed:
            wcache = model.init_cache(b, cap)
            if self.mesh is not None:
                from repro.sharding import specs as sh
                wcache = jax.device_put(wcache, sh.to_shardings(
                    self.mesh, sh.cache_specs(model.cfg, wcache, self.mesh)))
            wkey = jax.random.PRNGKey(0)
            wl, wcache = self._call(self._prefill, params, batch, wcache)
            wt = sample(wl, wkey, self.sampler)
            if max_new > 1:
                wl, wcache = self._call(self._decode, params, wt,
                                        jnp.asarray(extra), wcache)
                wt = sample(wl, wkey, self.sampler)
            wt.block_until_ready()
            del wcache
            self._warmed.add(sig)

        t0 = time.perf_counter()
        logits, cache = self._call(self._prefill, params, batch, cache)
        tok = sample(logits, key, self.sampler)
        tok.block_until_ready()
        ttft = time.perf_counter() - t0
        first_logits = logits

        # device-side accumulation: one host transfer AFTER the loop.  A
        # per-step np.asarray(tok) forces a device->host sync per token and
        # poisons decode_tps with transfer latency.
        out = [tok]
        t1 = time.perf_counter()
        pos = extra
        for i in range(max_new - 1):
            key = jax.random.fold_in(key, i)
            logits, cache = self._call(self._decode, params, tok,
                                       jnp.asarray(pos), cache)
            tok = sample(logits, key, self.sampler)
            out.append(tok)
            pos += 1
        if max_new > 1:
            tok.block_until_ready()
        dt = time.perf_counter() - t1
        tps = (b * (max_new - 1)) / dt if max_new > 1 and dt > 0 else 0.0
        tokens_out = np.asarray(jnp.stack(out, axis=1))
        # true prompt length: ``t`` counts pad_prompt's LEFT padding, which
        # over-counted per-token TTFT normalisation for ragged prompts —
        # subtract the batch's pad entry (one pad per batch by construction)
        pad = batch.get("pad")
        prompt_len = t - (int(np.asarray(pad).reshape(-1)[0])
                          if pad is not None else 0)
        return GenerationResult(tokens=tokens_out, ttft_s=ttft,
                                decode_tps=tps, prompt_len=prompt_len,
                                method=self.method, backend=self.backend,
                                logits=first_logits)

    # ------------------------------------------------------------------
    # continuous batching
    # ------------------------------------------------------------------
    def _continuous_fns(self, block_size: int, max_nb: int, b_prefill: int,
                        b_decode: int, num_blocks: int,
                        sel_on: bool = False):
        """Build (or fetch) the two jitted step functions for one static
        geometry: gather blocks -> model step -> sample -> scatter back.

        ``sel_on`` (host tier): the step fns additionally return the plans'
        per-logical-block selection counts ((rows, max_nb) int32, summed
        over layers — core/plan.py::pool_block_counts), the live signal the
        prefetch hook ranks host-tier staging by.  Same extra-jit-output
        pattern as obs; the sampled tokens are unaffected."""
        sig = (block_size, max_nb, b_prefill, b_decode, num_blocks, sel_on)
        if sig in self._cont_fns:
            return self._cont_fns[sig]
        from repro.serving import pool as pl
        model, method, backend = self.model, self.method, self.backend
        mesh = self.mesh
        chunk = model.cfg.quoka.chunk_size
        sampler = self.sampler
        # compiled-in telemetry: with a live registry the step fns return
        # the per-layer LayerObs pytree as an EXTRA jit output (device
        # scalars, fetched alongside the sampled tokens); without one they
        # compile exactly as before — bit-identical metrics-off compute
        obs_on = self._obs_on
        selb = (block_size, max_nb) if sel_on else None

        if mesh is not None:
            from repro.sharding import specs as sh

            def constrain(cache):
                # keep the gathered linear view on the canonical cache
                # layout (batch rows over FSDP axes, heads over model) —
                # without the constraint GSPMD can resolve the view to
                # replicated and gather/scatter stop being layout-local
                return sh.constrain_tree(
                    mesh, cache, sh.cache_specs(model.cfg, cache, mesh))
        else:
            def constrain(cache):
                return cache

        def prefill_step(p, data, table, tokens, start, vlen, key):
            cache = constrain(pl.gather(data, table, num_blocks, block_size))
            res = model.prefill_chunk(
                p, {"tokens": tokens}, start, cache, method,
                backend=backend, valid_len=vlen, with_obs=obs_on,
                sel_blocks=selb)
            last_h, cache = res[0], res[1]
            logits = model._readout(p, last_h[:, None, :])[:, 0]
            tok = sample(logits, key, sampler)
            wrote = jnp.where(vlen > 0, jnp.full_like(vlen, chunk), 0)
            touched = pl.touched_blocks(start, wrote, max_nb, block_size)
            data = pl.scatter(data, constrain(cache), table, touched,
                              num_blocks, block_size)
            return (data, tok) + tuple(res[2:])

        def decode_step(p, data, table, tokens, pos, live, key):
            cache = constrain(pl.gather(data, table, num_blocks, block_size))
            res = model.decode_step(p, tokens, pos, cache,
                                    method, backend=backend, with_obs=obs_on,
                                    sel_blocks=selb)
            logits, cache = res[0], res[1]
            tok = sample(logits, key, sampler)
            touched = pl.touched_blocks(pos, live, max_nb, block_size)
            data = pl.scatter(data, constrain(cache), table, touched,
                              num_blocks, block_size)
            return (data, tok) + tuple(res[2:])

        if mesh is None:
            fns = (jax.jit(prefill_step), jax.jit(decode_step))
        else:
            # donate + pin the pool pytree: the paged cache is by far the
            # largest resident buffer, and explicit in/out NamedShardings
            # keep its placement stable across steps instead of letting
            # propagation re-decide (and possibly reshard) per step fn
            from repro.sharding import specs as sh
            data_sh = sh.to_shardings(mesh, sh.cache_specs(
                model.cfg, self._pool_data_shapes(num_blocks, block_size),
                mesh, paged=True))
            rep = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            host = (rep,) * 4
            # `rep` broadcasts over the LayerObs pytree as an out-shardings
            # prefix: the per-layer stats are tiny replicated scalars
            out_sh = (data_sh, rep) + ((rep,) if obs_on else ()) \
                + ((rep,) if sel_on else ())
            fns = tuple(
                jax.jit(fn,
                        in_shardings=(self._param_sh, data_sh) + host + (rep,),
                        out_shardings=out_sh,
                        donate_argnums=(1,))
                for fn in (prefill_step, decode_step))
        self._cont_fns[sig] = fns
        return fns

    def _pool_data_shapes(self, num_blocks: int, block_size: int):
        """abstract pytree of the paged pool's device store (for specs)."""
        return jax.eval_shape(
            lambda: self.model.init_cache(num_blocks, block_size))

    def prefix_align(self) -> int:
        """Prefix-cache hit granularity: selection methods score per chunk,
        so hits must land on the B_CP grid to replay the exact computation;
        dense attention is chunking-invariant and shares at token
        granularity (COW partial tails)."""
        chunk = self.model.cfg.quoka.chunk_size
        return 1 if self.method == "full" else chunk

    def make_serve_state(self, requests: Sequence, *,
                         block_size: Optional[int] = None,
                         num_blocks: Optional[int] = None,
                         max_prefill_tokens: Optional[int] = None,
                         max_decode_batch: int = 8, key=None,
                         prefix_cache: bool = True,
                         host_tier_blocks: Optional[int] = None,
                         prefetch_depth: Optional[int] = None,
                         policy=None,
                         max_prefill_rows: Optional[int] = None) -> ServeState:
        """Size the pool/scheduler for a request trace and compile the two
        step functions (static geometry: chunk width, prefill rows, decode
        rows, blocks per request).

        ``host_tier_blocks`` > 0 turns on the hierarchical pool (demoted
        prefix blocks stay matchable on a host-memory tier; see
        serving/pool.py) and compiles the step functions with the
        selection-count prefetch oracle; ``prefetch_depth`` caps how many
        host blocks the per-step prefetch hook stages ahead of promotion.
        Both default from ``QuokaConfig``.

        ``policy`` (None | "fcfs" | "slo" | SchedPolicy) selects the
        scheduling policy (serving/policy.py); a preempting policy widens
        the per-request block geometry to the suspend/resume worst case.
        ``max_prefill_rows`` overrides the compiled prefill-row count
        (default: the full-chunk capacity ``max_prefill_tokens // chunk``);
        raise it to let short tail chunks — charged their real length —
        pack together."""
        from repro.serving.policy import resolve_policy
        from repro.serving.pool import PagedKVCache, max_blocks_bound
        from repro.serving.scheduler import Scheduler
        chunk = self.model.cfg.quoka.chunk_size
        block_size = block_size or chunk
        g = self.model.cfg.quoka.granularity
        if self.method != "full" and g > 1 and block_size % g != 0:
            raise ValueError(
                f"block_size={block_size} must be a multiple of the "
                f"selection granularity {g}: block-granular plans "
                f"materialize as whole-block sub-views of the paged pool "
                f"(serving/pool.py::gather_blocks), which needs the plan "
                f"grid to divide the pool grid")
        max_prefill_tokens = max_prefill_tokens or 4 * chunk
        pol = resolve_policy(policy)
        align = self.prefix_align() if prefix_cache else chunk
        max_nb = max(max_blocks_bound(r.prompt_len, r.max_new, chunk,
                                      block_size, align=align,
                                      preempt=pol.may_preempt)
                     for r in requests)
        if num_blocks is None:
            num_blocks = max_decode_batch * max_nb    # no contention
        b_p = (max(1, max_prefill_tokens // chunk)
               if max_prefill_rows is None else max(1, int(max_prefill_rows)))
        rows = b_p                  # scheduler cap (pre mesh-rounding)
        b_d = max_decode_batch
        if self.mesh is not None:
            # the pool's block axis shards over the FSDP axes — round the
            # pool and the step-ROW geometries up to the data-parallel
            # degree so every placement divides evenly instead of
            # replicating.  The scheduler's admission bound stays the
            # user's max_decode_batch; only the compiled decode batch
            # carries (idle) padding rows.
            from repro.sharding.specs import _axes_size, fsdp_axes
            dp = _axes_size(self.mesh, fsdp_axes(self.mesh))
            num_blocks = -(-num_blocks // dp) * dp
            b_p = -(-b_p // dp) * dp
            b_d = -(-b_d // dp) * dp
        qcfg = self.model.cfg.quoka
        htb = (int(getattr(qcfg, "host_tier_blocks", 0))
               if host_tier_blocks is None else int(host_tier_blocks))
        pfd = (int(getattr(qcfg, "prefetch_depth", 4))
               if prefetch_depth is None else int(prefetch_depth))
        pool = PagedKVCache(self.model, num_blocks, block_size,
                            mesh=self.mesh, host_tier_blocks=htb)
        # selection methods consume prefill in ``granularity``-sized score
        # units — that is the finest grid a packed chunk can be charged at
        grid = 1 if self.method == "full" else max(1, g)
        sched = Scheduler(pool, chunk, max_prefill_tokens, max_decode_batch,
                          prefix_cache=prefix_cache, prefix_align=align,
                          registry=self.registry, policy=pol,
                          max_prefill_rows=rows, token_grid=grid)
        fns = self._continuous_fns(block_size, max_nb, b_p, b_d, num_blocks,
                                   sel_on=htb > 0)
        key = key if key is not None else jax.random.PRNGKey(0)
        return ServeState(pool=pool, sched=sched, fns=fns, key=key,
                          chunk=chunk, max_nb=max_nb, b_prefill=b_p,
                          b_decode=b_d, host_tier=htb, prefetch_depth=pfd,
                          hot=np.zeros((max_nb,), np.float64))

    def _record_layer_obs(self, phase: str, lobs) -> None:
        """Feed one step's in-jit ``LayerObs`` pytree (per-layer device
        scalars, core/plan.py) into the registry: per-layer selected-KV
        fraction vs the budget ratio, plan refresh/reuse counts, and the
        score-distribution sketch.  NaN marks not-applicable (non-selecting
        blocks; budget/sketch on dense-fallback layers; sketch on plan-reuse
        steps) and is skipped.  One stacked host transfer per step."""
        reg = self.registry
        sel, ctx, bud, ref, lo, mean, hi = np.asarray(
            jnp.stack(lobs))                           # (7, n_layers)
        with np.errstate(invalid="ignore", divide="ignore"):
            frac = sel / ctx
            budf = bud / ctx
        for li in range(sel.shape[0]):
            if not np.isfinite(frac[li]):
                continue
            reg.set(f"select/layer{li:02d}/kv_fraction", frac[li])
            reg.observe("select/kv_fraction", frac[li])
            reg.observe(f"select/{phase}/kv_fraction", frac[li])
            if np.isfinite(budf[li]):
                reg.set(f"select/layer{li:02d}/budget_fraction", budf[li])
        fin = ref[np.isfinite(ref)]
        if fin.size:
            n_ref = float(fin.sum())
            reg.count("select/plan_refresh", n_ref)
            reg.count("select/plan_reuse", float(fin.size) - n_ref)
        for nm, v in (("score_lo", lo), ("score_mean", mean),
                      ("score_hi", hi)):
            v = v[np.isfinite(v)]
            if v.size:
                reg.observe(f"select/{nm}", float(v.mean()))

    def _note_hot(self, state: ServeState, sel, rows: int) -> None:
        """Fold one step's selection counts ((rows_compiled, max_nb) int32,
        the extra jit output) into the decayed per-logical-block hotness
        vector the prefetch hook ranks by.  Exponential decay keeps the
        ranking tracking the CURRENT working set's selection pattern."""
        counts = np.asarray(sel)[:rows].astype(np.float64).sum(axis=0)
        state.hot = 0.5 * state.hot + counts

    def _prefetch(self, state: ServeState) -> None:
        """Stage upcoming promotions' H2D copies while the step dispatched
        just above is still computing (double buffering: the copy for step
        N+1 overlaps step N's compute — the ``pool/h2d_stage`` span nests
        inside the step span, which is the trace-level proof of overlap).

        The oracle: the next waiting request's host-tier matches, ranked by
        the decayed QUOKA selection-count hotness of their LOGICAL block
        offsets (blocks whose positions the scoring pass keeps selecting
        get their bytes moved first), capped at ``prefetch_depth``.  Purely
        an ordering hint — promotion in ``alloc_prefix`` falls back to a
        synchronous-dispatch ``device_put`` for anything unstaged."""
        pool, sched = state.pool, state.sched
        if pool.host is None or state.prefetch_depth <= 0 \
                or not sched.waiting:
            return
        r = sched.waiting[0]
        fulls, tail = pool.match_prefix(r.tokens,
                                        chain=sched._chain.get(r.rid))
        cand = [(li, e[1]) for li, e in enumerate(fulls)
                if isinstance(e, tuple)]
        if tail is not None and isinstance(tail[0], tuple):
            cand.append((len(fulls), tail[0][1]))
        if not cand:
            return
        hot = state.hot
        cand.sort(key=lambda c: -(hot[c[0]] if c[0] < hot.shape[0]
                                  else 0.0))
        cand = cand[:state.prefetch_depth]
        with self.registry.span("pool/h2d_stage", blocks=len(cand)):
            n = sum(pool.stage(slot) for _, slot in cand)
        if n:
            self.registry.count("pool/staged", float(n))

    def _host_counters(self, state: ServeState) -> None:
        """Registry counters for the tier traffic of this step (deltas of
        the pool's monotonic totals)."""
        pool = state.pool
        cur = (pool.demoted, pool.promoted, pool.host_evictions,
               pool.staged_used)
        for name, now_v, prev in zip(
                ("pool/demoted", "pool/promoted", "pool/host_evictions",
                 "pool/staged_used"), cur, state.host_ctr):
            if now_v > prev:
                self.registry.count(name, float(now_v - prev))
        state.host_ctr = cur

    def step(self, state: ServeState) -> Tuple[int, int]:
        """One engine step: admit, run a mixed chunk-prefill step over up to
        ``max_prefill_tokens`` of pending prompt chunks, then a batched
        decode step over every active decode request.  Returns
        (prefill rows, decode rows) executed.  ``state.events`` is reset and
        filled with this step's emitted (rid, token) pairs — the feed
        ``serve_stream`` yields from."""
        pool, sched = state.pool, state.sched
        reg, obs = self.registry, self._obs_on
        state.events = []
        admitted = sched.admit(state.now)
        if obs:
            now = state.now
            for r in admitted:
                reg.observe("sched/admission_wait_s",
                            max(0.0, now - r.arrival_s))
            reg.set("sched/queue_depth", float(len(sched.waiting)))
            reg.set("sched/suspended", float(len(sched.suspended)))
            reg.set("sched/active", float(sched.n_active))
            reg.set("pool/occupancy", 1.0 - pool.num_free / pool.num_blocks)
            reg.set("pool/cached_blocks", float(pool.num_cached))
            if pool.host is not None:
                reg.set("pool/host_blocks", float(len(pool.host)))
        if pool.host is not None:
            self._host_counters(state)
        sel_at = 2 + (1 if obs else 0)     # extra-output slot (host tier)

        rows = sched.pack_prefill(state.now)
        if rows:
            tokens = np.zeros((state.b_prefill, state.chunk), np.int32)
            start = np.zeros((state.b_prefill,), np.int32)
            vlen = np.zeros((state.b_prefill,), np.int32)
            for i, (r, ch, st, vl) in enumerate(rows):
                tokens[i], start[i], vlen[i] = ch, st, vl
            table = pool.table_array([r.rid for r, *_ in rows],
                                     state.b_prefill, state.max_nb)
            state.key, k1 = jax.random.split(state.key)
            # the span brackets dispatch THROUGH the token fetch: with the
            # async runtime the np.asarray sync is where device time lands
            with reg.span("engine/prefill_step", rows=len(rows)):
                out = self._call(state.fns[0], self.params, pool.data,
                                 table, tokens, start, vlen, k1)
                pool.data, tok = out[0], out[1]
                # prefetch hook: dispatch next-step H2D stages BETWEEN the
                # step dispatch and the blocking token fetch, so the copies
                # run under the compute this step already queued
                self._prefetch(state)
                tok_np = np.asarray(tok)
            if obs:
                self._record_layer_obs("prefill", out[2])
                reg.count("engine/prefill_tokens", float(vlen.sum()))
            if state.host_tier:
                self._note_hot(state, out[sel_at], len(rows))
            now = state.now
            for i, (r, ch, st, vl) in enumerate(rows):
                ev = sched.note_prefilled(r, vl, int(tok_np[i]), now)
                if ev is not None:
                    state.events.append((r.rid, ev))
            state.prefill_steps += 1

        drows = sched.pack_decode()
        if drows:
            tokens = np.zeros((state.b_decode,), np.int32)
            pos = np.zeros((state.b_decode,), np.int32)
            live = np.zeros((state.b_decode,), np.int32)
            for i, r in enumerate(drows):
                tokens[i], pos[i], live[i] = r.out[-1], r.decode_pos, 1
            table = pool.table_array([r.rid for r in drows],
                                     state.b_decode, state.max_nb)
            state.key, k2 = jax.random.split(state.key)
            with reg.span("engine/decode_step", rows=len(drows)):
                out = self._call(state.fns[1], self.params, pool.data,
                                 table, tokens, pos, live, k2)
                pool.data, tok = out[0], out[1]
                self._prefetch(state)
                tok_np = np.asarray(tok)
            if obs:
                self._record_layer_obs("decode", out[2])
                reg.count("engine/decode_tokens", float(len(drows)))
            if state.host_tier:
                self._note_hot(state, out[sel_at], len(drows))
            now = state.now
            for i, r in enumerate(drows):
                state.events.append((r.rid,
                                     sched.note_decoded(r, int(tok_np[i]),
                                                        now)))
            # occupancy over the SCHEDULER's slot bound (the compiled row
            # batch may carry mesh-rounding padding rows)
            state.occupancy.append(len(drows) / sched.max_decode_batch)
            state.decode_steps += 1

        state.steps += 1
        return len(rows), len(drows)

    def serve_stream(self, requests: Sequence, *,
                     block_size: Optional[int] = None,
                     num_blocks: Optional[int] = None,
                     max_prefill_tokens: Optional[int] = None,
                     max_decode_batch: Optional[int] = None, key=None,
                     prefix_cache: Optional[bool] = None,
                     host_tier_blocks: Optional[int] = None,
                     prefetch_depth: Optional[int] = None,
                     policy=None, max_prefill_rows: Optional[int] = None,
                     state: Optional[ServeState] = None):
        """Streaming front-end of ``serve``: a generator yielding
        ``(rid, token)`` the step each token is emitted (the first token of
        a request right after its prefill completes, then one per decode
        step).  The generator's return value is the full ``ServeResult`` —
        ``serve()`` is exactly a drain of this stream.

        The idle wait is wakeup-correct for streaming consumers: the sleep
        until the next arrival is recomputed from the CURRENT clock every
        time the loop re-enters (a consumer may hold the generator between
        yields for arbitrarily long), and is capped at 0.25 s so a request
        arriving while the consumer processes tokens is admitted promptly
        rather than after a stale full-length sleep."""
        requests = list(requests)
        if not requests:
            return ServeResult({}, {}, {}, 0.0, 0, 0.0,
                               method=self.method, backend=self.backend)
        if state is None:
            state = self.make_serve_state(
                requests, block_size=block_size, num_blocks=num_blocks,
                max_prefill_tokens=max_prefill_tokens,
                max_decode_batch=(8 if max_decode_batch is None
                                  else max_decode_batch), key=key,
                prefix_cache=(True if prefix_cache is None
                              else prefix_cache),
                host_tier_blocks=host_tier_blocks,
                prefetch_depth=prefetch_depth, policy=policy,
                max_prefill_rows=max_prefill_rows)
        elif (block_size is not None or num_blocks is not None
              or max_prefill_tokens is not None or key is not None
              or max_decode_batch is not None or prefix_cache is not None
              or host_tier_blocks is not None or prefetch_depth is not None
              or policy is not None or max_prefill_rows is not None):
            # silently ignoring these would e.g. report cache-on numbers
            # for a prefix_cache=False A/B pass over a warm state
            raise ValueError(
                "serve(state=...) reuses the state's compiled geometry, "
                "cache configuration and policy; pass these options to "
                "make_serve_state instead")
        sched = state.sched
        if sched.pending():
            raise RuntimeError("serve state is mid-trace; drain it first")
        from repro.serving.pool import max_blocks_bound
        need = max(max_blocks_bound(r.prompt_len, r.max_new, state.chunk,
                                    state.pool.block_size,
                                    align=sched.prefix_align,
                                    preempt=sched.policy.may_preempt)
                   for r in requests)
        if need > state.max_nb:
            raise ValueError(
                f"trace needs {need} blocks/request > compiled geometry "
                f"{state.max_nb}; build a fresh state")
        live = {r.rid for r in requests}
        if len(live) != len(requests):
            raise ValueError("duplicate request ids in one trace")
        sched.done = []                     # per-trace completion list
        state.steps = state.prefill_steps = state.decode_steps = 0
        state.occupancy = []
        state.events = []
        pool = state.pool
        prefix0 = (pool.lookups, pool.hit_requests, pool.hit_tokens,
                   pool.prompt_tokens, pool.evictions, pool.cow_copies,
                   pool.demoted, pool.promoted, pool.host_evictions,
                   pool.staged_used)
        sched0 = (sched.preemptions, sched.resumes, sched.deadline_misses)
        pending = sorted(requests, key=lambda r: r.arrival_s)
        state.t0 = time.perf_counter()
        while pending or sched.pending():
            now = state.now
            while pending and pending[0].arrival_s <= now:
                sched.add(pending.pop(0))
            if not sched.pending():
                # idle: sleep until the next arrival instead of re-checking
                # the queue every 1 ms (a multi-second arrival gap used to
                # busy-spin ~1000 wakeups/s); the 0.25 s cap bounds clock
                # drift and keeps shutdown/interrupt latency sane.  Step
                # counts are untouched — only wakeups that packed nothing
                # are skipped (tests/test_scheduler.py asserts both).
                time.sleep(min(0.25, max(0.0, pending[0].arrival_s - now)))
                continue
            n_pf, n_dec = self.step(state)
            if n_pf == 0 and n_dec == 0 and sched.pending():
                raise RuntimeError(
                    "scheduler stall: pending requests but nothing packed")
            for ev in state.events:
                yield ev

        wall = state.now
        pool.check_invariants()
        assert pool.num_free + pool.num_evictable == pool.num_blocks, \
            "blocks leaked after drain"
        done = sched.done
        generated = sum(len(r.out) for r in done)
        hit_tok = pool.hit_tokens - prefix0[2]
        all_tok = pool.prompt_tokens - prefix0[3]
        # ``Engine.stats`` / ``ServeResult.prefix`` are REGISTRY VIEWS: the
        # per-serve prefix-cache stats land in gauges under serve/prefix/
        # (gauges, not counters — counters would accumulate across serve()
        # calls on one engine, while these are deltas of THIS trace) and are
        # read back as a flat suffix-keyed dict.  With metrics off an
        # ephemeral registry keeps the public dict shape identical.
        preg = self.registry if self._obs_on else obs_reg.Registry()
        sc = preg.scope("serve/prefix")
        sc.set("requests", pool.lookups - prefix0[0])
        sc.set("cache_hits", pool.hit_requests - prefix0[1])
        sc.set("hit_tokens", hit_tok)
        sc.set("prompt_tokens", all_tok)
        sc.set("hit_rate", hit_tok / all_tok if all_tok else 0.0)
        sc.set("evictions", pool.evictions - prefix0[4])
        sc.set("cow_copies", pool.cow_copies - prefix0[5])
        sc.set("cached_blocks", pool.num_cached)
        if pool.host is not None:
            sc.set("demoted", pool.demoted - prefix0[6])
            sc.set("promoted", pool.promoted - prefix0[7])
            sc.set("host_evictions", pool.host_evictions - prefix0[8])
            sc.set("staged_used", pool.staged_used - prefix0[9])
            sc.set("host_blocks", len(pool.host))
        self.stats = preg.view("serve/prefix")
        if self._obs_on:
            reg = self.registry
            for r in done:
                if r.ttft_s is not None:
                    reg.observe("serve/ttft_s", r.ttft_s)
                    reg.observe(f"tenant/{r.tenant}/ttft_s", r.ttft_s)
                dec = len(r.out) - 1
                if dec > 0 and r.done_s is not None and r.ttft_s is not None:
                    tpot = (r.done_s - r.arrival_s - r.ttft_s) / dec
                    reg.observe("serve/tpot_s", tpot)
                    reg.observe(f"tenant/{r.tenant}/tpot_s", tpot)
            reg.count("serve/requests_finished", float(len(done)))
            reg.count("serve/tokens_generated", float(generated))
            reg.event("serve_done", wall_s=wall, requests=len(done),
                      generated=generated,
                      tokens_per_s=generated / wall if wall > 0 else 0.0,
                      steps=state.steps,
                      prefill_steps=state.prefill_steps,
                      decode_steps=state.decode_steps,
                      method=self.method, backend=self.backend,
                      **{f"prefix_{k}": v for k, v in self.stats.items()})
        return ServeResult(
            tokens={r.rid: np.asarray(r.out, np.int32) for r in done},
            ttft_s={r.rid: r.ttft_s for r in done},
            latency_s={r.rid: r.done_s - r.arrival_s for r in done},
            wall_s=wall, generated=generated,
            tokens_per_s=generated / wall if wall > 0 else 0.0,
            steps=state.steps, prefill_steps=state.prefill_steps,
            decode_steps=state.decode_steps,
            occupancy=(float(np.mean(state.occupancy))
                       if state.occupancy else 0.0),
            method=self.method, backend=self.backend,
            cached_len={r.rid: r.cached_len for r in done},
            prefix=dict(self.stats),
            policy=sched.policy.name,
            preemptions=sched.preemptions - sched0[0],
            resumes=sched.resumes - sched0[1],
            deadline_misses=sched.deadline_misses - sched0[2])

    def serve(self, requests: Sequence, *, block_size: Optional[int] = None,
              num_blocks: Optional[int] = None,
              max_prefill_tokens: Optional[int] = None,
              max_decode_batch: Optional[int] = None, key=None,
              prefix_cache: Optional[bool] = None,
              host_tier_blocks: Optional[int] = None,
              prefetch_depth: Optional[int] = None,
              policy=None, max_prefill_rows: Optional[int] = None,
              state: Optional[ServeState] = None) -> ServeResult:
        """Serve a request trace with continuous batching.

        ``requests``: serving.request.Request objects (arrival_s offsets
        are honoured against the wall clock).  Each engine step packs up to
        ``max_prefill_tokens`` of pending prompt chunks plus every active
        decode token; admission ordering, prefill-packing order and
        preemption are delegated to ``policy`` (serving/policy.py — FCFS
        head-of-line by default, "slo" for EDF + weighted fairness +
        decode preemption) against pool capacity and the
        ``max_decode_batch`` batch-slot bound.  Greedy outputs under the
        default policy are token-identical to per-request ``generate``
        (tests/test_scheduler), including requests admitted via a
        prefix-cache hit (tests/test_prefix_cache).

        ``prefix_cache`` (default on) shares identical prompt prefixes
        across requests through the paged pool (multi-turn chats / shared
        system prompts skip re-prefilling cached blocks).  Pass a ``state``
        from ``make_serve_state`` to serve several traces over one warm
        pool — cached blocks of earlier traces stay matchable — as long as
        the new requests fit the compiled geometry.

        This is a drain of ``serve_stream``; use that directly to consume
        ``(rid, token)`` pairs as they are emitted."""
        stream = self.serve_stream(
            requests, block_size=block_size, num_blocks=num_blocks,
            max_prefill_tokens=max_prefill_tokens,
            max_decode_batch=max_decode_batch, key=key,
            prefix_cache=prefix_cache, host_tier_blocks=host_tier_blocks,
            prefetch_depth=prefetch_depth, policy=policy,
            max_prefill_rows=max_prefill_rows, state=state)
        while True:
            try:
                next(stream)
            except StopIteration as stop:
                return stop.value
