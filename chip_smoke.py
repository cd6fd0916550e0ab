#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-4b at its published widths on one TPU.

    python3 chip_smoke.py                          # one TPU chip
    python3 chip_smoke.py --mesh data=1,model=4    # sharded serve vs one chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal   # tiny widths

One process drives the chip through the normal serving entry points
(``Engine.serve`` over the paged pool and scheduler), with random bf16
weights made from ``--seed``.  Default phases:

  1. device   platform, device_kind and count; anything but a TPU fails
  2. params   qwen3-4b (36 layers, d 2560, 32/8 heads, head_dim 128,
              d_ff 9728, vocab 151936) created in bf16
  3. kernels  the compiled flash-attention and QUOKA scoring kernels
              against the XLA oracles at the model's widths
  4. serve    the same seeded requests (prompts of 2048-4096 tokens, 16 new
              tokens each) through a ``quoka`` engine on compiled Pallas
              kernels and a ``full`` engine: every request must finish with
              all its tokens, every quoka layer's selected-KV fraction must
              be below 1, and each engine's one-shot ``generate`` over two
              of the prompts must give finite first-token logits

``--mesh`` runs only the sharded quoka serve and the single-device quoka
engine it is compared with: the served first tokens must agree, and the
first-token logits must differ by less than bf16 drift allows and by less
than two different prompts' logits do.  ``--cpu-rehearsal`` runs the same
phases at smoke widths with interpreted kernels; it is never a chip result.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every check passed on an accelerator; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MAX_DECODE_BATCH = 2       # decode rows per step; also the prefill rows
N_REQUESTS = 4
LOGIT_ROWS = 2             # prompts in the one-shot first-token logits check
# sharded vs one-device first-token logits, ||d||_2 / ||l||_2.  Both run 36
# bf16 layers in different reduction orders, and the rounding grows with
# depth (and with flipped selections): 4.96e-2 on a v5e 2x2, up to 9.4e-2
# in a CPU probe.  A layout fault that mixes rows reads like the control,
# the same ratio between two different prompts' one-device logits.
LOGIT_RTOL = 0.2
FIRST_TOKEN_AGREE = 0.5    # served first tokens, sharded vs one-device
KERNEL_RTOL = 2e-2         # compiled kernel vs oracle, relative to max|ref|


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def check_kernels(cfg, backend: str, context: int, seed: int) -> list:
    """Compiled flash attention (prefill chunk over [budget | chunk], decode
    t = 1) and QUOKA scoring (T = ``context``) against the XLA oracles on
    random inputs at the model's widths.  Returns failure messages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops

    q_cfg = cfg.quoka
    h, n_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    chunk, budget, dt = q_cfg.chunk_size, q_cfg.budget, cfg.compute_dtype
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=dt):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    failures = []
    for name, b, t in (("flash prefill", 1, chunk), ("flash decode", 2, 1)):
        tk = budget + t
        q, k, v = normal((b, t, h, d)), normal((b, tk, n_kv, d)), \
            normal((b, tk, n_kv, d))
        valid = jax.random.bernoulli(next(keys), 0.9, (b, n_kv, tk))
        valid = valid.at[:, :, budget:].set(True)
        kw = dict(causal=True, boundary=budget)
        out = kops.attention(q, k, v, valid, backend=backend, **kw)
        with jax.default_matmul_precision("highest"):
            ref = kops.attention(q, k, v, valid, backend="xla", **kw)
        err = rel_err(out, ref)
        ok = bool(jnp.isfinite(out).all()) and err <= KERNEL_RTOL
        log(f"kernel     {name:14s} b={b} q={t} keys={tk}: max|err|/max|ref| "
            f"{err:.3e} ({'ok' if ok else 'FAIL'})")
        if not ok:
            failures.append(f"{name} kernel disagrees with its oracle")

    b, n_q = 2, q_cfg.n_queries
    qbar = normal((b, n_q, n_kv, d), jnp.float32)
    qbar = qbar / jnp.linalg.norm(qbar, axis=-1, keepdims=True)
    k = normal((b, context, n_kv, d))
    valid = jax.random.bernoulli(next(keys), 0.9, (b, context))
    out = np.asarray(kops.score(qbar, k, valid, backend=backend))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(kops.score(qbar, k, valid, backend="xla"))
    live = np.broadcast_to(np.asarray(valid)[:, None, :], ref.shape)
    err = rel_err(out[live], ref[live])
    ok = bool(np.array_equal(out[~live], ref[~live])) and err <= KERNEL_RTOL
    log(f"kernel     {'score':14s} b={b} n_q={n_q} T={context}: "
        f"max|err|/max|ref| {err:.3e} ({'ok' if ok else 'FAIL'})")
    if not ok:
        failures.append("score kernel disagrees with its oracle")
    return failures


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def engine_peak(before, after) -> str:
    """The device's ``peak_bytes_in_use`` only grows over a process, so one
    engine's own peak is known when its phase raised it, and bounded by the
    earlier peak when it did not."""
    if after is None:
        return "not reported"
    if before is None or after > before:
        return f"{after}"
    return f"at most {after} (did not raise the process peak)"


def first_token_logits(eng, prompts):
    """First-token logits (LOGIT_ROWS, V) from the engine's one-shot
    ``generate`` over the first LOGIT_ROWS prompts, each cut to the longest
    chunk multiple that all of them hold (so ``pad_prompt`` pads none)."""
    import numpy as np
    chunk = eng.model.cfg.quoka.chunk_size
    rows = prompts[:LOGIT_ROWS]
    t = min(len(p) for p in rows) // chunk * chunk
    batch = eng.pad_prompt(np.stack([p[:t] for p in rows]))
    return np.asarray(eng.generate(batch, 1).logits, np.float64)


def serve_engine(eng, prompts, max_new: int, chunk: int):
    """Warm-up (compiles both step programs at the trace's geometry), then
    the measured serve.  Returns (compile seconds, ServeResult)."""
    from repro.serving.request import make_requests

    kw = dict(max_decode_batch=MAX_DECODE_BATCH,
              max_prefill_tokens=MAX_DECODE_BATCH * chunk)
    reqs = make_requests(prompts, max_new)
    state = eng.make_serve_state(reqs, **kw)
    t0 = time.perf_counter()
    # one one-chunk request, two tokens: a prefill step and a decode step
    eng.serve(make_requests([prompts[0][:chunk]], 2), state=state)
    compile_s = time.perf_counter() - t0
    del state                               # free the warm-up pool
    if eng.registry.enabled:
        from repro.obs import Registry
        eng.registry = Registry()            # drop the warm-up's samples
    return compile_s, eng.serve(reqs, **kw)


def request_failures(name: str, res, n: int, max_new: int) -> list:
    bad = [rid for rid in range(n)
           if rid not in res.tokens or len(res.tokens[rid]) != max_new]
    return [f"{name}: requests {bad} did not finish with {max_new} tokens"] \
        if bad else []


def run_one_chip(model, params, prompts, max_new: int, backend: str,
                 device) -> list:
    import numpy as np

    from repro.obs import Registry
    from repro.serving.engine import Engine

    cfg = model.cfg
    chunk = cfg.quoka.chunk_size
    failures, engines = [], {}
    # both serves before either one-shot logit check, the smaller (quoka:
    # 10.34e9 against full's 10.41e9 B on a v5e) first, so that each
    # serve's peak shows as its own rise
    for method in ("quoka", "full"):
        eng = Engine(model, params, method=method, backend=backend,
                     registry=Registry())
        if eng.backend != backend:
            failures.append(f"{method}: engine resolved backend "
                            f"{eng.backend!r}, not {backend!r}")
            continue
        engines[method] = eng
        before = peak_bytes(device)
        compile_s, res = serve_engine(eng, prompts, max_new, chunk)
        ttft = np.percentile(list(res.ttft_s.values()), 50)
        log(f"serve      {method:5s} backend {eng.backend}: compile "
            f"{compile_s:.1f} s, TTFT p50 {ttft * 1e3:.1f} ms, "
            f"{res.tokens_per_s:.1f} tokens/s ({res.generated} tokens, "
            f"{res.wall_s:.2f} s, {res.prefill_steps} prefill / "
            f"{res.decode_steps} decode steps), peak_bytes_in_use "
            f"{engine_peak(before, peak_bytes(device))}")
        failures += request_failures(method, res, len(prompts), max_new)
        if method == "quoka":
            gauges = eng.registry.snapshot()["gauges"]
            fr = [gauges.get(f"select/layer{li:02d}/kv_fraction", np.nan)
                  for li in range(cfg.n_layers)]
            log(f"select     quoka kv_fraction per layer (last step): "
                f"min {np.nanmin(fr):.4f} max {np.nanmax(fr):.4f}")
            if not all(f < 1.0 for f in fr):
                failures.append("quoka: a layer's select/kv_fraction is not "
                                "below 1")
        del res
    for method, eng in engines.items():
        before = peak_bytes(device)
        logits = first_token_logits(eng, prompts)
        finite = bool(np.isfinite(logits).all())
        log(f"logits     {method:5s} one-shot first-token logits "
            f"{logits.shape}: {'finite' if finite else 'NOT FINITE'}, "
            f"max|l| {np.abs(logits).max():.3f}, peak_bytes_in_use "
            f"{engine_peak(before, peak_bytes(device))}")
        if not finite:
            failures.append(f"{method}: first-token logits are not finite")
    return failures


def run_mesh(model, params, prompts, spec: str, max_new: int,
             backend: str) -> list:
    """Sharded quoka serve vs the single-device quoka engine."""
    import numpy as np

    from repro.launch.mesh import mesh_from_spec
    from repro.serving.engine import Engine

    chunk = model.cfg.quoka.chunk_size
    mesh = mesh_from_spec(spec)
    log(f"mesh       {dict(mesh.shape)} over {mesh.size} devices")
    out, failures = {}, []
    for name, m in (("one-device", None), ("sharded", mesh)):
        eng = Engine(model, params, method="quoka", backend=backend, mesh=m)
        compile_s, res = serve_engine(eng, prompts, max_new, chunk)
        ttft = np.percentile(list(res.ttft_s.values()), 50)
        log(f"serve      {name:10s} compile {compile_s:.1f} s, TTFT p50 "
            f"{ttft * 1e3:.1f} ms, {res.tokens_per_s:.1f} tokens/s")
        failures += request_failures(name, res, len(prompts), max_new)
        out[name] = (res.tokens, first_token_logits(eng, prompts))
        del eng, res
    (t1, l1), (t2, l2) = out["one-device"], out["sharded"]
    err = float(np.linalg.norm(l2 - l1) / np.linalg.norm(l1))
    # what a row mix-up reads: two different prompts' one-device logits
    control = float(np.linalg.norm(l1[1] - l1[0]) / np.linalg.norm(l1[0]))
    rids = [r for r in sorted(t1) if r in t2]
    first = float(np.mean([t1[r][0] == t2[r][0] for r in rids])) \
        if rids else 0.0
    agree = [float(np.mean(t1[r] == t2[r])) for r in rids]
    log(f"compare    first-token logits ||d||/||l|| {err:.3e} (bound "
        f"{LOGIT_RTOL}; two prompts' logits read {control:.3e}), "
        f"max|d|/max|l| {rel_err(l2, l1):.3e}; served first tokens agree "
        f"{first:.3f} (bound {FIRST_TOKEN_AGREE}); greedy tokens agree "
        f"{np.mean(agree):.3f} (per request {[round(a, 3) for a in agree]})")
    if not (np.isfinite(l2).all() and err <= LOGIT_RTOL):
        failures.append("sharded first-token logits differ from the "
                        "single-device engine's beyond bf16 drift")
    if control <= LOGIT_RTOL:
        failures.append("two prompts' logits differ by less than the bound: "
                        "the logit check cannot tell a fault from rounding")
    if first < FIRST_TOKEN_AGREE:
        failures.append("the sharded serve's first tokens disagree with the "
                        "single-device engine's")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="data=N,model=M",
                    help="run only the sharded serve and its single-device "
                         "comparison on this mesh")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="smoke widths on any device, interpreted kernels; "
                         "not a chip result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np

    dev = jax.devices()[0]
    count = len(jax.devices())
    log(f"device     platform {dev.platform}, kind {dev.device_kind}, "
        f"count {count}")
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              f"--cpu-rehearsal runs smoke widths off the chip",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch import compile_cache
    from repro.models.model import build_model

    log(f"cache      compilation cache at {compile_cache.enable()}")
    cfg = get_config("qwen3-4b")
    backend = "pallas"
    if args.cpu_rehearsal:
        cfg, backend = cfg.smoke(), "pallas_interpret"
    max_new = 4 if args.cpu_rehearsal else 16
    model = build_model(cfg)

    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(args.seed),
                        dtype=cfg.compute_dtype)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"params     {cfg.name}: {cfg.n_layers} layers, d {cfg.d_model}, "
        f"{n_params} parameters, {n_bytes} bytes in {cfg.dtype} "
        f"({time.perf_counter() - t0:.1f} s incl. compile)")

    budget = cfg.quoka.budget
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(2 * budget, 4 * budget + 1, N_REQUESTS)
    prompts = [rng.integers(3, cfg.vocab, (int(n),)).astype(np.int32)
               for n in lens]
    log(f"requests   {N_REQUESTS} prompts of {sorted(int(n) for n in lens)} "
        f"tokens, {max_new} new tokens each")

    if args.mesh:
        failures = run_mesh(model, params, prompts, args.mesh, max_new,
                            backend)
    else:
        chunk = cfg.quoka.chunk_size
        context = -(-(max(lens) + max_new) // chunk) * chunk
        failures = check_kernels(cfg, backend, context, args.seed)
        failures += run_one_chip(model, params, prompts, max_new, backend,
                                 dev)

    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        log("rehearsal passed (interpreted kernels at smoke widths; "
            "not a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
