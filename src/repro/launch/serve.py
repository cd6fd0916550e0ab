"""Serving launcher: batched chunked-prefill + decode with QUOKA selection.

One-shot batch mode (TTFT / decode throughput, paper §4.6):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --prompt-len 1024 --max-new 32 --method quoka

Continuous-batching trace mode (paged KV pool + chunked-prefill/decode
scheduler + cross-request prefix caching; Poisson arrivals):

    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --n-requests 16 --rate 8 --max-decode-batch 8

Prefix-cache-heavy traces — a shared system prompt, or multi-turn
conversations whose every turn re-sends the growing conversation:

    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --trace shared --shared-len 256 --n-requests 8
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --trace multiturn --turns 4 --turn-gap 0.5 [--no-prefix-cache]

Sharded serving (params/caches/paged pool placed per sharding/specs.py,
QUOKA scoring T-local per shard; token-identical to single-device):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --smoke --continuous \
        --mesh data=2,model=4

Loads a checkpoint if given (random init otherwise — latency numbers are
weight-independent) and reports TTFT / throughput / batch occupancy.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.selection import METHODS
from repro.launch import compile_cache
from repro.models.model import build_model
from repro.serving.engine import Engine
from repro.serving.request import make_requests
from repro.serving.sampler import SamplerConfig
from repro.training import checkpoint as ckpt


def _build_trace(model, args, rng):
    """(prompts, arrivals, extras) for one of four trace shapes — extras is
    a dict of per-request ``make_requests`` kwargs (tenants / priorities /
    deadlines), empty for the single-tenant traces:

      poisson    independent random prompts, Poisson arrivals (--rate)
      shared     every prompt opens with one shared system prompt of
                 --shared-len tokens (the cross-request prefix-cache case)
      multiturn  conversations of --turns turns; each turn's prompt extends
                 the previous turn's prompt with fresh tokens, arriving
                 --turn-gap seconds apart (synthetic: extensions are random
                 tokens, not the model's own replies — latency is
                 weight-independent either way)
      multi_tenant  a background tenant floods long prompts at t=0 while an
                 interactive tenant's short prompts arrive at --rate
                 carrying --ttft-deadline; pair with --policy slo to see
                 EDF + preemption protect the interactive TTFT
    """
    vocab = model.cfg.vocab
    if args.trace == "multi_tenant":
        n_bg = max(1, args.n_requests // 4)
        n_int = max(1, args.n_requests - n_bg)
        bg = [rng.integers(3, vocab, (args.prompt_len,)).astype(np.int32)
              for _ in range(n_bg)]
        ilen = max(1, args.prompt_len // 8)
        inter = [rng.integers(3, vocab,
                              (int(rng.integers(max(1, ilen // 2),
                                                ilen + 1)),)).astype(np.int32)
                 for _ in range(n_int)]
        rate = args.rate if not np.isinf(args.rate) else 1000.0
        arrivals = np.concatenate(
            [np.zeros(n_bg), np.cumsum(rng.exponential(1.0 / rate, n_int))])
        extras = dict(
            tenants=["background"] * n_bg + ["interactive"] * n_int,
            priorities=[0] * n_bg + [1] * n_int,
            ttft_deadlines=[None] * n_bg + [args.ttft_deadline] * n_int)
        return bg + inter, arrivals, extras
    if args.trace in ("poisson", "shared"):
        arrivals = (np.zeros(args.n_requests) if np.isinf(args.rate)
                    else np.cumsum(rng.exponential(1.0 / args.rate,
                                                   args.n_requests)))
        if args.trace == "poisson":
            lens = rng.integers(max(1, args.prompt_len // 2),
                                args.prompt_len + 1, args.n_requests)
            prompts = [rng.integers(3, vocab, (int(n),)).astype(np.int32)
                       for n in lens]
        else:
            sys_tok = rng.integers(3, vocab,
                                   (args.shared_len,)).astype(np.int32)
            sfx = max(1, args.prompt_len - args.shared_len)
            prompts = [np.concatenate(
                [sys_tok, rng.integers(3, vocab,
                                       (int(rng.integers(1, sfx + 1)),)
                                       ).astype(np.int32)])
                for _ in range(args.n_requests)]
        return prompts, arrivals, {}
    assert args.trace == "multiturn"
    n_conv = max(1, args.n_requests // args.turns)
    ext = max(1, args.prompt_len // (2 * args.turns))
    prompts, arrivals = [], []
    for c in range(n_conv):
        start = (0.0 if np.isinf(args.rate)
                 else float(rng.exponential(args.turns / args.rate)) * c)
        cur = rng.integers(3, vocab,
                           (args.prompt_len // 2,)).astype(np.int32)
        for t in range(args.turns):
            if t:
                cur = np.concatenate(
                    [cur, rng.integers(3, vocab, (ext,)).astype(np.int32)])
            prompts.append(cur.copy())
            arrivals.append(start + t * args.turn_gap)
    return prompts, np.asarray(arrivals), {}


def _print_telemetry(reg):
    """Human-readable digest of the serve registry (full detail goes to the
    --trace-dir exports)."""
    snap = reg.snapshot()
    h, g = snap["histograms"], snap["gauges"]
    kv = h.get("select/kv_fraction")
    if kv and kv["count"]:
        print(f"{'telemetry':10s} selected-KV fraction mean {kv['mean']:.3f} "
              f"p50 {kv['p50']:.3f} min {kv['min']:.3f} "
              f"over {kv['count']} layer-steps "
              f"({100 * (1 - kv['mean']):.0f}% of KV skipped on average)")
    for nm in ("engine/prefill_step", "engine/decode_step",
               "sched/admission_wait_s"):
        s = h.get(nm)
        if s and s["count"]:
            print(f"{'telemetry':10s} {nm}: p50 {s['p50']*1e3:7.1f} ms  "
                  f"p99 {s['p99']*1e3:7.1f} ms  n {s['count']}")
    if "pool/occupancy" in g:
        print(f"{'telemetry':10s} pool occupancy {g['pool/occupancy']:.2f}  "
              f"cached blocks {g.get('pool/cached_blocks', 0):.0f}")


def _export_telemetry(reg, trace_dir, prefix):
    from repro.obs import export_all
    paths = export_all(reg, trace_dir, prefix=prefix)
    for kind, p in sorted(paths.items()):
        print(f"{'telemetry':10s} {kind} -> {p}")


def run_continuous(model, params, args, mesh=None):
    """Trace-driven continuous batching with prefix caching (see
    --trace / --no-prefix-cache)."""
    rng = np.random.default_rng(0)
    prompts, arrivals, extras = _build_trace(model, args, rng)
    reg = None
    if args.metrics or args.trace_dir:
        from repro.obs import Registry
        reg = Registry()
    eng = Engine(model, params, method=args.method, mesh=mesh,
                 sampler=SamplerConfig(temperature=args.temperature),
                 registry=reg)
    kw = dict(block_size=args.block_size, num_blocks=args.num_blocks,
              max_prefill_tokens=args.max_prefill_tokens,
              max_decode_batch=args.max_decode_batch,
              prefix_cache=not args.no_prefix_cache,
              host_tier_blocks=args.host_tier_blocks,
              prefetch_depth=args.prefetch_depth,
              policy=args.policy)
    # compile warmup with the REAL step geometry: the jit cache is keyed on
    # max_nb/num_blocks, which derive from the longest prompt and max_new
    longest = max(prompts, key=len)
    eng.serve(make_requests([longest] * 2, args.max_new), **kw)
    if reg is not None:
        # the step functions were compiled telemetry-on and read
        # ``eng.registry`` at runtime, so swapping in a fresh registry
        # drops the warmup trace's samples without recompiling
        from repro.obs import Registry
        reg = eng.registry = Registry()
    res = eng.serve(make_requests(prompts, args.max_new, arrivals=arrivals,
                                  **extras), **kw)
    ttft = np.asarray(sorted(res.ttft_s.values()))
    print(f"{args.method:10s} {res.generated} tokens / {res.wall_s:.2f} s "
          f"= {res.tokens_per_s:8.1f} tok/s   "
          f"TTFT p50 {np.percentile(ttft, 50)*1e3:7.1f} ms   "
          f"p99 {np.percentile(ttft, 99)*1e3:7.1f} ms   "
          f"occupancy {res.occupancy:.2f}   "
          f"steps {res.steps} ({res.prefill_steps} prefill / "
          f"{res.decode_steps} decode)")
    print(f"{'policy':10s} {res.policy}: {res.preemptions} preemptions, "
          f"{res.resumes} resumes, {res.deadline_misses} deadline misses")
    if extras:
        by_tenant = {}
        for r in make_requests(prompts, args.max_new, arrivals=arrivals,
                               **extras):
            if r.rid in res.ttft_s:
                by_tenant.setdefault(r.tenant, []).append(res.ttft_s[r.rid])
        for t, vals in sorted(by_tenant.items()):
            v = np.asarray(vals)
            print(f"{'tenant':10s} {t}: TTFT p50 "
                  f"{np.percentile(v, 50)*1e3:7.1f} ms   p99 "
                  f"{np.percentile(v, 99)*1e3:7.1f} ms   n {len(v)}")
    s = res.prefix
    if s:
        print(f"{'cache':10s} {s['cache_hits']:.0f}/{s['requests']:.0f} "
              f"requests hit, {s['hit_tokens']:.0f}/{s['prompt_tokens']:.0f} "
              f"prompt tokens served from cache ({100 * s['hit_rate']:.1f}%), "
              f"{s['evictions']:.0f} evictions, "
              f"{s['cow_copies']:.0f} COW copies")
        if "demoted" in s:
            print(f"{'host tier':10s} {s['demoted']:.0f} demoted, "
                  f"{s['promoted']:.0f} promoted "
                  f"({s['staged_used']:.0f} from prefetch staging), "
                  f"{s['host_evictions']:.0f} host evictions, "
                  f"{s['host_blocks']:.0f} blocks resident")
    if reg is not None:
        _print_telemetry(reg)
        if args.trace_dir:
            _export_telemetry(reg, args.trace_dir, f"serve_{args.method}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--method", default="quoka", choices=METHODS)
    ap.add_argument("--budget", type=int, default=None)
    ap.add_argument("--budget-ratio", type=float, default=None,
                    help="B_SA as a fraction of the prompt (paper Table 2)")
    ap.add_argument("--prompt-len", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--compare-dense", action="store_true")
    # continuous-batching trace mode
    ap.add_argument("--continuous", action="store_true",
                    help="serve a Poisson trace with the paged-pool "
                         "scheduler instead of one synchronous batch")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=float("inf"),
                    help="Poisson arrival rate, requests/s (inf = all at 0)")
    ap.add_argument("--trace", default="poisson",
                    choices=("poisson", "shared", "multiturn",
                             "multi_tenant"),
                    help="trace shape: independent prompts, a shared "
                         "system prompt, multi-turn conversations (those "
                         "two exercise the prefix cache), or a background "
                         "tenant's long prompts vs an interactive tenant's "
                         "deadline-carrying short prompts (--policy slo)")
    ap.add_argument("--policy", default="fcfs", choices=("fcfs", "slo"),
                    help="scheduling policy: FCFS head-of-line (default) "
                         "or SLO-aware (EDF admission over TTFT deadlines, "
                         "per-tenant weighted fairness, preemption of "
                         "running decodes via block suspend/resume)")
    ap.add_argument("--ttft-deadline", type=float, default=0.5,
                    help="TTFT deadline (s) tagged onto the interactive "
                         "tenant's requests (--trace multi_tenant)")
    ap.add_argument("--shared-len", type=int, default=512,
                    help="shared system-prompt tokens (--trace shared)")
    ap.add_argument("--turns", type=int, default=4,
                    help="turns per conversation (--trace multiturn)")
    ap.add_argument("--turn-gap", type=float, default=0.5,
                    help="seconds between a conversation's turns "
                         "(--trace multiturn)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request KV prefix caching")
    ap.add_argument("--block-size", type=int, default=None,
                    help="KV pool block size (default: chunk_size)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size (default: fits max-decode-batch)")
    ap.add_argument("--host-tier-blocks", type=int, default=None,
                    help="hierarchical pool: host-memory tier capacity in "
                         "blocks (evicted prefix blocks demote there and "
                         "stay matchable; 0 disables, default: config). "
                         "Pair with an undersized --num-blocks to exercise "
                         "demotion")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="host-tier blocks staged (async H2D) per engine "
                         "step ahead of promotion, ranked by the QUOKA "
                         "selection-count oracle (default: config)")
    ap.add_argument("--max-prefill-tokens", type=int, default=None,
                    help="prompt tokens packed per engine step "
                         "(default: 4 * chunk_size)")
    ap.add_argument("--max-decode-batch", type=int, default=8)
    ap.add_argument("--metrics", action="store_true",
                    help="serve-path telemetry (obs/): step spans, "
                         "scheduler/pool counters and the in-jit per-layer "
                         "selected-KV fraction; prints a digest after the "
                         "run.  Off by default — the metrics-off serve "
                         "path is bit-identical to pre-telemetry builds")
    ap.add_argument("--trace-dir", default=None,
                    help="export telemetry (implies --metrics) to DIR: "
                         "JSONL event log, Prometheus text dump and a "
                         "Chrome/Perfetto trace of the engine's step spans; "
                         "one-shot mode instead captures a device timeline "
                         "there via jax.profiler.trace")
    ap.add_argument("--mesh", default=None, metavar="data=N,model=M",
                    help="serve sharded on a device mesh: params/caches/"
                         "paged pool placed per sharding/specs.py, QUOKA "
                         "scoring T-local per shard.  The axis product "
                         "must equal the visible device count (CPU: set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N before launch)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke(n_layers=4, d_model=256, n_heads=8, n_kv_heads=2,
                        d_ff=512, vocab=2048)
    q = cfg.quoka
    if args.budget:
        q = dataclasses.replace(q, budget=args.budget)
    if args.budget_ratio:
        q = dataclasses.replace(q, budget_ratio=args.budget_ratio)
    cfg = dataclasses.replace(cfg, quoka=dataclasses.replace(
        q, chunk_size=min(q.chunk_size, args.prompt_len)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=cfg.compute_dtype)
    if args.ckpt:
        params = ckpt.restore(args.ckpt, params)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import mesh_from_spec
        mesh = mesh_from_spec(args.mesh)
        print(f"# mesh {dict(mesh.shape)} over {mesh.size} devices")

    if args.continuous:
        run_continuous(model, params, args, mesh=mesh)
        return

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(3, cfg.vocab,
                                    (args.batch, args.prompt_len)), jnp.int32)
    methods = [args.method] + (["full"] if args.compare_dense else [])
    for m in methods:
        eng = Engine(model, params, method=m, mesh=mesh,
                     sampler=SamplerConfig(temperature=args.temperature))
        eng.generate({"tokens": toks}, 2)          # compile warmup
        if args.trace_dir:
            # one-shot mode: capture the device timeline (the named_scope
            # markers in kernels/ops.py + core/plan.py label the regions)
            with jax.profiler.trace(args.trace_dir):
                r = eng.generate({"tokens": toks}, args.max_new)
            print(f"# jax profiler trace -> {args.trace_dir}")
        else:
            r = eng.generate({"tokens": toks}, args.max_new)
        print(f"{m:18s} TTFT {r.ttft_s*1e3:9.1f} ms   "
              f"decode {r.decode_tps:8.1f} tok/s   "
              f"prompt {args.prompt_len} × {args.batch}")


if __name__ == "__main__":
    main()
