"""Where the serving step's time is named: the scope vocabulary inside the
two jitted step programs, the engine's host phase spans and each
request's spans, and the mapping of the registry's clock onto a profiler
trace's (``bench/scopes.py``)."""
import importlib.util
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analysis.hlo import _OP_NAME_RE
from repro.configs import get_config
from repro.models.model import build_model
from repro.obs import NULL, Registry
from repro.serving.engine import Engine
from repro.serving.request import make_requests

BENCH = Path(__file__).resolve().parents[1] / "bench"

# the scopes every step program carries, and those of the selection path
BASE_SCOPES = ("pool_gather", "pool_scatter", "embed", "attn_proj",
               "kv_write", "mlp", "readout", "sample", "stack")
QUOKA_SCOPES = ("plan_scores", "plan_topk", "plan_materialize",
                "ops_attention_xla", "ops_score_xla")


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("qwen3-4b").smoke()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def _step_hlo(smoke, method: str, program: str) -> str:
    """The lowered HLO of one step program, at a geometry where selection
    engages in both programs (views longer than budget + chunk)."""
    cfg, model, p = smoke
    eng = Engine(model, p, method=method, backend="xla")
    state = eng.make_serve_state(make_requests(_prompts(cfg, [96]), 4),
                                 max_decode_batch=2)
    chunk, nb = state.chunk, state.max_nb
    key = jax.random.PRNGKey(1)
    if program == "prefill":
        rows = state.b_prefill
        args = (np.zeros((rows, nb), np.int32),
                np.zeros((rows, chunk), np.int32),
                np.full((rows,), 64, np.int32),
                np.full((rows,), chunk, np.int32))
        fn = state.fns[0]
    else:
        rows = state.b_decode
        args = (np.zeros((rows, nb), np.int32), np.zeros((rows,), np.int32),
                np.full((rows,), 80, np.int32), np.ones((rows,), np.int32))
        fn = state.fns[1]
    return fn.lower(p, state.pool.data, *args, key).as_text(
        dialect="hlo", debug_info=True)


@pytest.mark.parametrize("method", ["quoka", "full"])
@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_step_program_carries_the_scope_vocabulary(smoke, program, method):
    text = _step_hlo(smoke, method, program)
    comps = {c for name in _OP_NAME_RE.findall(text) for c in name.split("/")}
    want = BASE_SCOPES + (QUOKA_SCOPES if method == "quoka" else ())
    missing = [s for s in want if s not in comps]
    assert not missing, missing
    if method == "full":
        assert not any(c.startswith("plan_") for c in comps)


def _serve(eng, cfg, arrivals=(0.0, 0.0)):
    reqs = make_requests(_prompts(cfg, [40, 24]), 3, arrivals=list(arrivals))
    eng.serve(reqs, max_decode_batch=2)
    return reqs


def _inside(inner, outer):
    return outer["ts"] - 1 <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_engine_records_phase_and_request_spans(smoke):
    cfg, model, p = smoke
    reg = Registry()
    eng = Engine(model, p, method="quoka", registry=reg)
    _serve(eng, cfg)                     # compile before the timed trace
    reg.trace_events.clear()
    reqs = _serve(eng, cfg, arrivals=(0.0, 0.6))
    evs = reg.trace_events
    names = {e["name"] for e in evs}
    assert {"engine/admit", "engine/inputs", "engine/prefill_step",
            "engine/decode_step", "engine/fetch", "engine/emit",
            "engine/idle_wait"} <= names
    steps = [e for e in evs if e["name"] in ("engine/prefill_step",
                                             "engine/decode_step")]
    # the token fetch nests inside a step span; the other phases do not
    for e in evs:
        if e["name"] == "engine/fetch":
            assert any(_inside(e, s) for s in steps)
        elif e["name"] in ("engine/admit", "engine/inputs", "engine/emit",
                           "engine/idle_wait"):
            assert not any(_inside(e, s) for s in steps)
    # three request spans per request, sharing its rid, end to end
    for r in reqs:
        got = {e["name"]: e for e in evs if e["name"].startswith("request/")
               and e["args"]["rid"] == r.rid}
        assert set(got) == {"request/queued", "request/prefill",
                            "request/decode"}
        q, pf, dc = (got[n] for n in ("request/queued", "request/prefill",
                                      "request/decode"))
        assert q["ts"] + q["dur"] == pytest.approx(pf["ts"], abs=1e-3)
        assert pf["ts"] + pf["dur"] == pytest.approx(dc["ts"], abs=1e-3)
        assert min(q["dur"], pf["dur"], dc["dur"]) >= 0
        assert r.admitted_s is not None and r.admitted_s >= r.arrival_s
        assert pf["dur"] * 1e-6 == pytest.approx(
            r.ttft_s - (r.admitted_s - r.arrival_s), abs=1e-6)
    assert reg.histograms["request/prefill"].count == 2 * len(reqs)


def test_null_registry_records_nothing_and_compiles_no_counters(smoke):
    cfg, model, p = smoke
    eng = Engine(model, p, method="quoka")
    assert eng.registry is NULL and eng.layer_obs is False
    _serve(eng, cfg)
    assert not NULL.trace_events and not NULL.histograms
    # a registry attached after construction gets host spans only: the
    # step programs were built without the in-jit counters
    reg = Registry()
    eng.registry = reg
    _serve(eng, cfg)
    assert reg.histograms["engine/prefill_step"].count > 0
    assert reg.histograms["request/prefill"].count == 2
    assert not any(k.startswith("select/") for k in reg.histograms)
    # a registry passed at construction compiles the counters in
    reg2 = Registry()
    on = Engine(model, p, method="quoka", registry=reg2)
    assert on.layer_obs is True
    _serve(on, cfg)
    assert reg2.histograms["select/prefill/kv_fraction"].count > 0


def _bench_module(name):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_registry_spans_map_onto_the_profiler_clock(smoke, tmp_path):
    """Every registry span is also a profiler annotation; after the offset
    that the matched step spans fix, each lies within 1 ms of its twin."""
    scopes = _bench_module("scopes")
    trace_reduce = scopes.trace_reduce
    cfg, model, p = smoke
    reg = Registry()
    eng = Engine(model, p, method="quoka", registry=reg)
    _serve(eng, cfg)                     # compile outside the trace
    reg.trace_events.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(eng, cfg, arrivals=(0.0, 0.3))
    finally:
        jax.profiler.stop_trace()
    trace = scopes.load(trace_reduce.find_xplane(str(tmp_path)))
    off = scopes.clock_offset_ns(reg.trace_events, trace["host"])
    assert off is not None
    for name in ("engine/prefill_step", "engine/fetch", "engine/emit",
                 "engine/idle_wait"):
        mine = [[e["ts"] * 1e3 + off, e["dur"] * 1e3]
                for e in reg.trace_events if e["name"] == name]
        theirs = sorted([s, d] for n, s, d in trace["host"] if n == name)
        assert len(mine) == len(theirs) > 0, name
        for (a, da), (b, db) in zip(sorted(mine), theirs):
            assert abs(a - b) < 1e6 and abs((a + da) - (b + db)) < 1e6
