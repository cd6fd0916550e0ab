"""Device time by scope (``bench/scopes.py``) and the four readers built on
it, on synthetic traces and on a slice of a recorded v5e trace of
``qwen3-4b.long_prefill`` that keeps each op's scope path.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import scopes  # noqa: E402
import spec  # noqa: E402

SLICE = BENCH / "testdata" / "trace_v5e_scopes.json"


def test_scope_path_reads_the_vocabulary_from_op_names():
    assert scopes.scope_path(
        "jit(prefill_step)/jit(main)/stack/while/body/closed_call/"
        "attn_proj/dot_general") == ("stack", "attn_proj")
    assert scopes.scope_path(
        "jit(decode_step)/stack/while/body/plan_scores/ops_score_pallas/"
        "transpose") == ("stack", "plan_scores", "ops_score")
    assert scopes.scope_path("jit(prefill_step)/while/body/dynamic_slice") \
        == ()
    # `stack` is kept only on the scan's slices of weights and cache views
    # and its write-back; anything else in a layer with no scope of its
    # own has none
    body = "jit(prefill_step)/stack/while/body/"
    for tail in ("dynamic_slice", "closed_call/squeeze",
                 "dynamic_update_slice"):
        assert scopes.scope_path(body + tail) == ("stack",)
    for tail in ("closed_call/dot_general", "closed_call/concatenate"):
        assert scopes.scope_path(body + tail) == ()
    assert scopes.scope_path("jit(prefill_step)/stack/while") == ()
    assert scopes.scope_path(body + "mlp/dynamic_slice") == ("stack", "mlp")
    # a Pallas kernel has no op_name; its pallas_call name places it
    assert scopes.op_scope("flash_attention_bhtd.3", "") == \
        ("ops_attention",)
    assert scopes.op_scope("quoka_score_bhtd", "") == \
        ("plan_scores", "ops_score")
    assert scopes.op_scope("copy.7", "") == ()
    layers = {m["layer"] for m in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]}
    assert set(scopes.LAYER_OF.values()) <= layers


def _synthetic():
    ops = [["fusion.1", 10, 4, "jit(prefill_step)/pool_gather/gather"],
           ["while.2", 14, 20, "jit(prefill_step)/stack/while"],
           ["fusion.3", 14, 5, "jit(prefill_step)/stack/while/body/"
                               "plan_scores/reduce"],
           ["quoka_score_bhtd", 19, 3, ""],
           ["fusion.4", 22, 2, "jit(prefill_step)/stack/while/body/"
                               "plan_topk/sort"],
           ["copy.5", 24, 6, ""],
           ["fusion.6", 30, 4, "jit(prefill_step)/pool_scatter/scatter"],
           ["fusion.7", 40, 5, "jit(decode_step)/pool_gather/gather"],
           ["fusion.8", 45, 5, "jit(decode_step)/mlp/dot"],
           # a run that ends after the window's close
           ["fusion.9", 60, 10, "jit(decode_step)/mlp/dot"]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": [["jit_prefill_step(1)", 10, 24],
                                     ["jit_decode_step(2)", 40, 10],
                                     ["jit_decode_step(2)", 60, 10]]}],
            "host": [["bench/serve_loop", 0, 55],
                     ["engine/prefill_step", 9, 26],
                     ["engine/decode_step", 39, 12]]}


def test_program_tables_charge_each_op_to_its_innermost_scope():
    t = scopes.program_tables(_synthetic())
    pf, dc = t["prefill_step"], t["decode_step"]
    assert pf["runs"] == 1 and dc["runs"] == 1      # the late run is out
    ms = {k: v * 1e6 for k, v in pf["ms"].items()}
    assert ms == pytest.approx({"pool_gather": 4, "plan_scores": 5,
                                "ops_score": 3, "plan_topk": 2,
                                scopes.NONE: 6, "pool_scatter": 4})
    assert pf["op_ms"] * 1e6 == pytest.approx(24)   # the while is left out
    assert pf["unscoped_share"] == pytest.approx(6 / 24)
    assert pf["unscoped_top"][0][0] == "copy"
    # each op once, at any depth
    sel = scopes.ms_under(t, "prefill_step",
                          ("plan_scores", "plan_topk", "plan_materialize"))
    assert sel * 1e6 == pytest.approx(10)
    pool = scopes.ms_under(t, "decode_step", ("pool_gather", "pool_scatter"))
    assert pool * 1e6 == pytest.approx(5)
    assert scopes.ms_under(t, "decode_step", ("plan_topk",)) is None
    assert scopes.program_tables({"devices": [], "host": []}) == {}


def test_clock_offset_finds_the_traced_run_of_step_spans():
    reg = [{"name": n, "ts": ts, "dur": 10.0}
           for ts, n in [(100.0, "engine/prefill_step"),
                         (200.0, "engine/decode_step"),
                         (300.0, "engine/prefill_step"),
                         (400.0, "engine/decode_step"),
                         (520.0, "engine/decode_step")]]
    # the trace saw the last three, on a clock 5 ms ahead
    host = [["engine/prefill_step", 305e3, 10e3],
            ["engine/decode_step", 405e3 + 20, 10e3],
            ["engine/decode_step", 525e3, 10e3]]
    off = scopes.clock_offset_ns(reg, host)
    assert off == pytest.approx(5e3, abs=30)
    assert scopes.clock_offset_ns(reg, []) is None
    req = scopes.on_trace(
        [{"name": "request/queued", "ts": 50.0, "dur": 300.0,
          "args": {"rid": 1}}], off, "request/")
    assert req[0][1] == pytest.approx(55e3, abs=30)


def test_idle_by_request_state():
    trace = {"devices": [{"name": "d", "ops": [["f", 10, 10, ""],
                                               ["f", 30, 10, ""]],
                          "modules": []}],
             "host": [["bench/serve_loop", 0, 50]]}
    req = [["request/queued", 0, 12, {}], ["request/prefill", 12, 18, {}]]
    got = scopes.idle_by_request_state(trace, req)
    # gaps [0,10) queued, [20,30) in prefill, [40,50) none in flight
    assert got == pytest.approx({"request/queued": 10e-9,
                                 "request/prefill": 10e-9,
                                 "no request in flight": 10e-9})


class _View:
    """What ``bench/run.py``'s ``RunView`` gives a reader."""

    def __init__(self, trace=None, events=()):
        self.trace = trace
        self.notes = {}
        self._events = list(events)

    def spans(self, name):
        return [e["dur"] * 1e-6 for e in self._events if e["name"] == name]


def _reader(name):
    return spec.load_metric_reader(name)


def test_readers_read_the_tables_and_keep_silent_without_them():
    t = _synthetic()
    view = _View(trace={"modules": {}})
    view._scopes = {"trace": t, "tables": scopes.program_tables(t)}
    assert _reader("pool.gather_scatter_ms.prefill")(view) * 1e6 == \
        pytest.approx(8)
    assert _reader("pool.gather_scatter_ms.decode")(view) * 1e6 == \
        pytest.approx(5)
    assert _reader("select.plan_ms")(view) * 1e6 == pytest.approx(10)
    untraced = _View()
    for name in ("pool.gather_scatter_ms.prefill",
                 "pool.gather_scatter_ms.decode", "select.plan_ms",
                 "sched.service_p95_ms"):
        assert _reader(name)(untraced) is None
    spans = [{"name": "request/prefill", "ts": 0.0, "dur": d * 1e3}
             for d in range(1, 21)]
    assert _reader("sched.service_p95_ms")(_View(events=spans)) == \
        pytest.approx(19.05)


@pytest.fixture(scope="module")
def recorded():
    return json.loads(SLICE.read_text())


def test_recorded_slice_scopes_most_device_time(recorded):
    t = scopes.program_tables(recorded)
    assert set(t) == {"prefill_step", "decode_step"}
    for prog, tab in t.items():
        assert tab["runs"] >= 1
        assert sum(tab["ms"].values()) == pytest.approx(tab["op_ms"])
        assert tab["op_ms"] <= tab["module_ms"] * 1.001
        assert tab["unscoped_share"] < 0.10, (prog, tab["unscoped_top"])
        assert scopes.ms_under(t, prog, ("pool_gather", "pool_scatter")) > 0
    assert scopes.ms_under(t, "prefill_step",
                           ("plan_scores", "plan_topk",
                            "plan_materialize")) > 0


def test_recorded_slice_maps_registry_spans_onto_annotations(recorded):
    reg = recorded["registry"]
    off = scopes.clock_offset_ns(reg, recorded["host"])
    assert off is not None
    for name in scopes.STEP_SPANS:
        mine = sorted(e["ts"] * 1e3 + off for e in reg if e["name"] == name)
        theirs = sorted(s for n, s, _ in recorded["host"] if n == name)
        assert theirs
        for s in theirs:      # each annotation has a registry twin
            assert min(abs(s - m) for m in mine) < 1e6
