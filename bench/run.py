#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload qwen3-4b.long_prefill --seed 7 \\
        --seconds 51 --trace 0

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  One run:

  set-up   bf16 weights drawn on the device from ``--seed``; the serving
           engine with the cell's method on the compiled Pallas kernels;
           its two step programs compiled (or loaded from the checkout's
           compile cache) at the geometry that the mix's declared maxima
           fix, and driven once on a short warm-up trace
  window   ``Engine.serve_stream`` fed open-loop: ``rate * seconds``
           requests due in ``[0, seconds)``, each token stamped as it is
           yielded; cells that judge TTFT then drain what is left
  check    a sample of the finished requests, drawn from the seed with the
           longest among them, against the plain reference
           (``bench/reference.py``): by how much a served token's logit
           lies below the reference's best one, averaged over the
           sample's served tokens (``logit_gap_mean``)

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` reports
the per-layer metrics (``bench/metrics/<name>.py``) with a breakdown.  A
traced window drives the same compiled programs as an untraced one: the
program's registry records its host spans and counters only, and a
profiler traces the window's last seconds, stopped once the window's
requests have drained.  The in-jit selection counters, which add outputs
to the step programs, are read after the window closes, on a replay of
the window's prompts (prefill only) through a second engine that has
them compiled in.  The last line of stdout is one JSON object; the
numbers compared, each with its limit, are the last lines of stderr.
Without a TPU (or with fewer chips than the cell asks for) it prints no
result and exits with 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
OUT_DIR = ROOT / ".bench_out"

import flops as flops_mod  # noqa: E402
import serve  # noqa: E402
import spec as spec_mod  # noqa: E402
import traffic as traffic_mod  # noqa: E402

# profiler: the window's last stretch, a share of --seconds up to 6 s
TRACE_LEN, TRACE_MAX_S = 0.12, 6.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def pct(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


class StepLog:
    """Wraps the two compiled step programs of a serve state: each call is
    counted (``bench/flops.py``), annotated on the profiler's clock as
    ``bench/dispatch_prefill#<i>`` / ``bench/dispatch_decode#<i>``, and can
    start or stop the profiler.  Used in traced runs only."""

    def __init__(self, state, dims):
        self.dims, self.on_step = dims, None
        self.records = {"prefill": [], "decode": []}
        pf, dc = state.fns
        state.fns = (self._wrap("prefill", pf), self._wrap("decode", dc))

    def _wrap(self, kind, fn):
        from jax.profiler import TraceAnnotation
        count = flops_mod.prefill_step if kind == "prefill" \
            else flops_mod.decode_step

        def call(p, data, table, tokens, a, b, key):
            if self.on_step is not None:
                self.on_step()
            rec = count(self.dims, a, b)
            recs = self.records[kind]
            rec["i"] = len(recs)
            recs.append(rec)
            with TraceAnnotation(f"bench/dispatch_{kind}#{rec['i']}"):
                return fn(p, data, table, tokens, a, b, key)
        return call

    def clear(self):
        self.records = {"prefill": [], "decode": []}


class Profiler:
    """Starts ``jax.profiler`` once the window has run ``start`` seconds
    and marks the window's close on the trace (``bench/window_close``),
    both checked at each step.  ``finish`` stops it: it holds the host
    for seconds while the trace is written, so it is called once the
    window's requests have drained, when nothing measured is left."""

    def __init__(self, state, start: float, close: float, out: Path):
        self.state, self.start, self.close = state, start, close
        self.out, self.on, self.closed, self.done = out, False, False, False
        self.start_s = self.stop_s = None     # host time the calls took

    def tick(self):
        if self.done or self.closed:
            return
        now = time.perf_counter() - self.state.t0
        if not self.on and now >= self.start:
            import jax
            shutil.rmtree(self.out, ignore_errors=True)
            # no Python function tracing: it stalls the serve loop
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            t = time.perf_counter()
            jax.profiler.start_trace(str(self.out), profiler_options=opts)
            self.start_s = time.perf_counter() - t
            self.on = True
        elif self.on and now >= self.close:
            from jax.profiler import TraceAnnotation
            with TraceAnnotation("bench/window_close"):
                self.closed = True

    def finish(self):
        if self.on and not self.done:
            import jax
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t
        self.done = True


def _sample(done, seed: int, check: dict):
    """The requests to compare: the longest finished one, then others in
    an order drawn from the seed, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    if not done:
        return []
    rng = traffic_mod.rng_for(seed, tag=2)
    longest = max(done, key=lambda r: (r.prompt_len + len(r.out), -r.rid))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, tokens = [longest], len(longest.out)
    for r in rest:
        if tokens >= int(check["min_tokens"]) \
                or len(out) >= int(check["max_requests"]):
            break
        out.append(r)
        tokens += len(r.out)
    return out


def compare(params, config, traffic, geo, sample, control: bool = False):
    """Per served token, how far its logit lies below the plain
    reference's best; with ``control`` also the gap of the token that the
    control (the reference in float8) puts first.  Returns (program gaps,
    control gaps)."""
    import jax
    import numpy as np

    import reference as ref
    p_max, o_max = traffic_mod.bounds(traffic)
    rs = ref.spec_for(config, p_max + o_max, o_max, geo["capacity"])
    cs = ref.spec_for(config, p_max + o_max, o_max, geo["capacity"],
                      low=True)
    gaps, ctrl = [], []
    for r in sample:
        served = np.asarray(r.out, np.int64)
        lg = np.asarray(jax.device_get(ref.logits(params, r.tokens, served,
                                                  rs)), np.float64)
        best = lg.max(-1)
        gaps.append(best - lg[np.arange(len(served)), served])
        if control:
            cl = np.asarray(jax.device_get(ref.logits(
                params, r.tokens, served, cs)))
            pick = cl.argmax(-1)
            ctrl.append(best - lg[np.arange(len(served)), pick])
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0))
    return cat(gaps), cat(ctrl)


class RunView:
    """What a per-layer metric reader sees of one traced run."""

    def __init__(self, registry, steps, trace, peaks, admission_waits,
                 replay, ttft=()):
        self.registry = registry          # the window's Registry (host)
        self.steps = steps                # StepLog.records
        self.trace = trace                # trace_reduce.reduce() or None
        self.peaks = peaks
        # seconds from each request's due time to its admission
        self.admission_waits = admission_waits
        self.replay = replay              # Registry of the counters' replay
        # seconds from each request's due time to its first token, every
        # request due in the window
        self.ttft = list(ttft)
        self.notes = {}                   # readers' side findings

    def spans(self, name: str):
        """Durations in seconds of the program's ``name`` spans in the
        window."""
        return [ev["dur"] * 1e-6 for ev in self.registry.trace_events
                if ev["name"] == name]

    def histogram(self, name: str):
        """A histogram of the program's in-jit counters (the replay)."""
        return None if self.replay is None \
            else self.replay.histograms.get(name)


def _watch_admissions(sched):
    """Records, at each admission, how long the request waited since its
    due time, on the engine's clock (the ``now`` it admits at)."""
    waits = []
    admit = sched.admit

    def call(now=0.0):
        got = admit(now)
        waits.extend(max(0.0, now - r.arrival_s) for r in got)
        return got
    sched.admit = call
    return waits


def replay_counters(model, params, prog, backend, geo, bound, reqs):
    """The program's in-jit selection counters over the prefill of
    ``reqs``' prompts, one at a time, one first token each, through an
    engine built with a live registry, so that its step programs carry the
    counters, at the geometry that ``bound`` (the mix's longest request)
    fixes.  Run once the window's state is freed; returns that registry."""
    import numpy as np

    from repro.obs import Registry
    from repro.serving.engine import Engine
    from repro.serving.request import Request

    reg = Registry()
    eng = Engine(model, params, method=prog["method"], backend=backend,
                 registry=reg)
    replay = [Request(rid=r.rid, tokens=np.asarray(r.tokens), max_new=1)
              for r in reqs]
    state = eng.make_serve_state(
        [bound], max_decode_batch=geo["decode_rows"],
        max_prefill_tokens=geo["max_prefill_tokens"],
        num_blocks=geo["num_blocks"])
    assert state.max_nb == geo["max_nb"], (state.max_nb, geo)
    # one prompt at a time: the counters are means over a step's rows, so
    # each step then reads one chunk's fraction, whatever the window packed
    for r in replay:
        eng.serve([r], state=state)
    return reg


def run_cell(cell, seed: int, seconds: float, trace: bool, *, peaks: dict,
             t_start: float, backend: str = None, rate: float = None,
             fault=None, control: bool = False, device=None,
             check: bool = True) -> dict:
    """One run of ``cell``.  Returns ``{"line": result, "compared": [...],
    "diag": {...}}``; ``fault(state)`` may break the timed path
    underneath (tests); ``check=False`` skips the comparison with the
    reference (a rate sweep), and the run is then not correct."""
    import jax
    import numpy as np

    import weights as weights_mod
    from repro.models.model import build_model
    from repro.obs import Registry
    from repro.serving.engine import Engine
    from repro.serving.request import Request

    cfgf, tr = cell.config, cell.traffic
    prog = cfgf["program"]
    mcfg = serve.model_config(cfgf)
    geo = serve.geometry(cfgf, tr)
    dims = flops_mod.Dims.from_config(cfgf, geo["capacity"])
    model = build_model(mcfg)
    t_model = time.perf_counter()
    params = weights_mod.make(model, seed, mcfg.compute_dtype)
    jax.block_until_ready(params)
    backend = backend or prog["backend"]
    t_params = time.perf_counter()
    eng = Engine(model, params, method=prog["method"], backend=backend)
    reg = None
    if trace:
        # host spans and scheduler counters only: the engine was built
        # without a registry, so its step programs carry no in-jit
        # counters and are the untraced run's programs
        reg = Registry()
        eng.registry = reg
    p_max, o_max = traffic_mod.bounds(tr)
    bound = Request(rid=-1, tokens=np.full((p_max,), 3, np.int32),
                    max_new=o_max)
    state = eng.make_serve_state(
        [bound], max_decode_batch=geo["decode_rows"],
        max_prefill_tokens=geo["max_prefill_tokens"],
        num_blocks=geo["num_blocks"])
    assert state.max_nb == geo["max_nb"], (state.max_nb, geo)
    steps = StepLog(state, dims) if trace else None
    waits = _watch_admissions(state.sched)
    if fault is not None:
        fault(state)

    warm = [Request(rid=10 ** 6 + i, tokens=a.prompt, max_new=a.max_new)
            for i, a in enumerate(traffic_mod.warmup(tr, mcfg.vocab))]
    t_engine = time.perf_counter()
    eng.serve(warm, state=state)
    t_warm = time.perf_counter()
    # the pool stamps recycled blocks with a program compiled per power of
    # two of the block count: compile each here (ids out of range, so the
    # stamps change nothing) rather than in the window
    n = 1
    while True:
        state.pool._stamp([geo["num_blocks"]] * min(n, geo["num_blocks"]))
        if n >= geo["num_blocks"]:
            break
        n *= 2
    jax.block_until_ready(state.pool.data)
    prof = None
    if trace:
        prof = Profiler(state,
                        seconds - min(TRACE_LEN * seconds, TRACE_MAX_S),
                        seconds, OUT_DIR / "trace")
        steps.on_step = prof.tick
        for store in (reg.counters, reg.gauges, reg.histograms):
            store.clear()
        reg.trace_events.clear()
        reg.events.clear()
        steps.clear()
    waits.clear()

    arrivals = traffic_mod.generate(tr, seed, seconds, mcfg.vocab, rate=rate)
    reqs = [Request(rid=i, tokens=a.prompt, max_new=a.max_new,
                    arrival_s=a.arrival_s) for i, a in enumerate(arrivals)]
    drain_s = float(tr.get("drain_s", 0))
    compiles = _compile_counter()
    gcs = _gc_watch()
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    # where set-up went: process start to the model's build (Python and
    # JAX imports, the TPU runtime), weights, engine and serve state,
    # warm-up, the pool's stamp programs
    setup_phases = {"start_s": t_model - t_start,
                    "weights_s": t_params - t_model,
                    "engine_s": t_engine - t_params,
                    "warmup_s": t_warm - t_engine,
                    "stamps_s": t_open - t_warm}
    stamps = {r.rid: [] for r in reqs}
    events = []                        # every token's stamp, in order
    stream = eng.serve_stream(reqs, state=state)
    from jax.profiler import TraceAnnotation
    try:
        while True:
            with TraceAnnotation("bench/serve_loop"):
                ev = next(stream, None)
            if ev is None:
                break
            now = time.perf_counter() - state.t0
            stamps[ev[0]].append(now)
            events.append(now)
            if now > seconds + drain_s or (drain_s <= 0 and now > seconds):
                break
    finally:
        stream.close()
        if prof:
            prof.finish()
    wall = time.perf_counter() - state.t0
    compile_events = compiles()
    gc_n, gc_s, gc_max = gcs()
    jax.block_until_ready(state.pool.data)
    dev = device or jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # ---- end-to-end ----------------------------------------------------
    # a request with no first token by the end counts the wait so far
    ttft = [r.ttft_s if r.ttft_s is not None else wall - r.arrival_s
            for r in reqs]
    in_window = [t for ts in stamps.values() for t in ts if t <= seconds]
    itl = [b - a for ts in stamps.values() for a, b in zip(ts, ts[1:])
           if b <= seconds]
    finished = [r for r in reqs if r.status == "done"]
    wrong_len = [r.rid for r in finished if len(r.out) != r.max_new]
    unfinished = [r.rid for r in reqs if r.status != "done"]
    failed = len(wrong_len) + (len(unfinished) if drain_s > 0 else 0)
    e2e = {
        "ttft_p50_ms": pct(ttft, 50) * 1e3 if ttft else None,
        "ttft_p95_ms": pct(ttft, 95) * 1e3 if ttft else None,
        "itl_p95_ms": pct(itl, 95) * 1e3 if itl else None,
        "output_tokens_per_s": len(in_window) / seconds,
        "setup_s": setup_s,
    }
    backlog = sum(1 for r in reqs if r.ttft_s is None
                  or r.arrival_s + r.ttft_s > seconds)
    half = [[r.ttft_s for r in reqs if r.ttft_s is not None
             and (r.arrival_s < seconds / 2) == first] for first in (1, 0)]
    diag = {"requests": len(reqs), "finished": len(finished),
            "unfinished": len(unfinished), "wall_s": wall,
            "tokens_in_window": len(in_window), "itl_samples": len(itl),
            "backlog_at_close": backlog,
            "ttft_p50_first_half_ms": pct(half[0], 50) * 1e3 if half[0]
            else None,
            "ttft_p50_second_half_ms": pct(half[1], 50) * 1e3 if half[1]
            else None,
            "compiles_in_window": compile_events["compile"][0],
            "compile_path_in_window": compile_events,
            "gc_in_window": {"count": gc_n, "seconds": gc_s,
                             "longest_s": gc_max},
            "ttft_ms": [None if r.ttft_s is None else r.ttft_s * 1e3
                        for r in reqs],
            "longest_host_gaps_s": sorted(
                (b - a for a, b in zip(events, events[1:])),
                reverse=True)[:5],
            "rate_per_s": len(reqs) / seconds,
            "queue_wait_p95_ms": pct(waits, 95) * 1e3 if waits else None,
            "setup_phases": setup_phases}

    # ---- per-layer (traced) --------------------------------------------
    per_layer, breakdown, dev_extra = {}, None, {}
    if trace:
        import trace_reduce
        diag["profiler_start_s"] = prof.start_s
        diag["profiler_stop_s"] = prof.stop_s
        red = None
        xp = trace_reduce.find_xplane(str(OUT_DIR / "trace"))
        if xp is not None:
            t = trace_reduce.load(xp)
            red = trace_reduce.reduce(t)
            red["host"] = t["host"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            dev_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        step_records = steps.records

    # ---- correctness ---------------------------------------------------
    done = [r for r in finished if len(r.out) == r.max_new]
    sample = _sample(done, seed, tr["check"]) if check else []
    # free the program's state before the replay and the reference run on
    # the chip
    del stream, state, eng, steps, prof
    gc.collect()
    if trace:
        t = time.perf_counter()
        due = [r for r in reqs if r.arrival_s < seconds]
        replay = replay_counters(model, params, prog, backend, geo,
                                 bound, due)
        diag["replay_s"] = time.perf_counter() - t
        view = RunView(reg, step_records, red, peaks, list(waits), replay,
                       ttft)
        for m in cell.per_layer:
            v = spec_mod.load_metric_reader(m["name"])(view)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        diag.update(view.notes)
        del replay
        gc.collect()
    gaps, ctrl = compare(params, cfgf, tr, geo, sample, control=control)
    compared = judge(cell, gaps, failed)
    correct = all(c[3] for c in compared)
    diag.update(gap_stats("logit_gap", gaps))
    if control:
        # the control in the program's place, judged by the same numbers
        control_compared = judge(cell, ctrl, failed)
        diag.update(gap_stats("control_gap", ctrl))
        diag["control_correct"] = all(c[3] for c in control_compared)
    diag["compared_requests"] = len(sample)

    metrics = per_layer if trace else {
        m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
        for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    device_info = {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": mem}
    device_info.update(dev_extra)
    line = {"correct": bool(correct), "attempted": len(reqs),
            "failed": int(failed), "metrics": metrics, "device": device_info}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {n: {"value": v, "limit": l}
                        for n, v, l, _ in compared}
    return {"line": line, "compared": compared, "diag": diag,
            "e2e": e2e, "gaps": gaps, "control": ctrl}


def judge(cell, gaps, failed: int):
    """The numbers that decide ``correct``, as (name, number, limit,
    holds): the mean logit gap of the compared tokens and the failed
    requests are upper limits, the number of compared tokens a lower
    one."""
    lim = cell.limits.get("logit_gap_mean", {}).get("limit")
    mean_gap = float(gaps.mean()) if gaps.size else math.inf
    need = int(cell.traffic["check"]["min_tokens"])
    return [
        ("logit_gap_mean", mean_gap, lim,
         lim is not None and math.isfinite(mean_gap) and mean_gap <= lim),
        ("failed_requests", failed, 0, failed == 0),
        ("compared_tokens_at_least", int(gaps.size), need, gaps.size >= need),
    ]


def gap_stats(prefix: str, gaps) -> dict:
    """Mean, widest, 99th percentile and the shares of tokens more than
    0.25, 0.5 and 1 below the reference's best logit."""
    import numpy as np
    if not gaps.size:
        return {}
    out = {f"{prefix}_mean": float(gaps.mean()),
           f"{prefix}_max": float(gaps.max()),
           f"{prefix}_p99": float(np.percentile(gaps, 99))}
    for t in (0.25, 0.5, 1.0):
        out[f"{prefix}_share_over_{t}"] = float((gaps > t).mean())
    return out


_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def _compile_counter():
    """Counts, from now on, the compile path's events: jaxpr traces,
    lowerings, XLA compilations and persistent-cache loads, each with its
    seconds.  Call the result to read ``{kind: [count, seconds]}``."""
    import jax
    seen = {k: [0, 0.0] for k in _COMPILE_EVENTS.values()}

    def listen(event, duration, **kw):
        kind = _COMPILE_EVENTS.get(event)
        if kind is not None:
            seen[kind][0] += 1
            seen[kind][1] += duration
    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: {k: list(v) for k, v in seen.items()}


def _gc_watch():
    """Times Python's garbage collections from now on; call the result to
    stop and read ``(count, seconds, longest)``."""
    import gc
    pauses, start = [], [0.0]

    def cb(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - start[0])
    gc.callbacks.append(cb)

    def read():
        gc.callbacks.remove(cb)
        return len(pauses), sum(pauses), max(pauses, default=0.0)
    return read


def chip(cell, device_list):
    """(device, peaks) of an accelerator that can run ``cell``, or the
    reason it cannot."""
    dev = device_list[0]
    if dev.platform != "tpu":
        return None, f"JAX found no TPU (platform {dev.platform!r})"
    if len(device_list) < cell.chips:
        return None, (f"the cell asks for {cell.chips} chips, JAX found "
                      f"{len(device_list)}")
    peaks = spec_mod.load_json(HERE / "peaks.json")
    if dev.device_kind not in peaks:
        return None, (f"no peaks for device kind {dev.device_kind!r} in "
                      f"bench/peaks.json")
    return (dev, peaks[dev.device_kind]), None


def enable_cache() -> str:
    """The program's persistent compilation cache (inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program of
    the run, the small ones too, so that only a cell's first run
    compiles."""
    import jax

    from repro.launch import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache.enable()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec_mod.load_cell(args.workload)
    import jax
    found, why = chip(cell, jax.devices())
    if found is None:
        log(f"bench/run.py: {why}; no result")
        return 2
    dev, peaks = found
    t_runtime = time.perf_counter()
    enable_cache()
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   peaks=peaks, t_start=T_START, device=dev)
    # of set-up's start: process start through the TPU runtime's start
    res["diag"]["setup_phases"]["runtime_s"] = t_runtime - T_START
    log("diag " + json.dumps(res["diag"]))
    for name, value, limit, ok in res["compared"]:
        log(f"compare {name} {value} limit {limit} "
            f"{'holds' if ok else 'FAILS'}")
    print(json.dumps(res["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
