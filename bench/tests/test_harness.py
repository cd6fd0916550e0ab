"""Tests of the benchmark harness, on the CPU at tiny widths.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They check the pieces the chip runs rest on: the trace reduction on a
recorded trace, the operation counts against hand counts, the traffic
generator, the configuration files against the program's presets, the
discovery of a cell's files by name, ``BENCHMARK.json`` against the
benchmark's contract, and whole runs of a tiny cell through
``run.run_cell``: sound, with the float8 control, and with the timed path
broken underneath.  No test here prints a chip result: ``run.py`` itself
exits with 2 off the TPU.
"""
from __future__ import annotations

import json
import math
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import faults  # noqa: E402
import flops  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# ---------------------------------------------------------------------------
# BENCHMARK.json against the contract
# ---------------------------------------------------------------------------

def test_benchmark_json_keeps_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert 1 <= b["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        names.add(c["name"])
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(b["workloads"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        cells = m.get("workloads", [w["name"] for w in b["workloads"]])
        for w in cells:               # each cell reports what it moves
            assert "workloads" not in e2e[m["moves"]] \
                or w in e2e[m["moves"]]["workloads"]
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        lim = cell.limits["logit_gap_mean"]
        assert lim["lower_reading"] < lim["limit"] < lim["upper_reading"]


# ---------------------------------------------------------------------------
# configurations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["qwen3-4b", "granite-3-2b",
                                  "qwen3-4b-dense"])
def test_config_widths_match_the_program(name):
    import serve
    from repro.configs import get_config
    f = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg = serve.model_config(f)
    base = get_config(f["program"]["arch"])
    for field in ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
                  "vocab", "rope_theta", "tie_embeddings"):
        assert getattr(cfg, field) == getattr(base, field), field
    assert cfg.resolved_head_dim == base.resolved_head_dim
    assert cfg.quoka.chunk_size == f["program"]["chunk_size"]
    assert cfg.quoka.budget == f["program"]["budget"]


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["long_prefill", "short_chat"])
def test_traffic_is_fixed_work_per_seed(mix):
    tr = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    big = 2 ** 31 + 977
    a = traffic.generate(tr, big, 51, vocab=1000)
    b = traffic.generate(tr, big, 51, vocab=1000)
    c = traffic.generate(tr, 5, 51, vocab=1000)
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    assert [x.arrival_s for x in a] == [x.arrival_s for x in b]
    # every seed serves the same schedule; the seed draws the tokens
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [x.arrival_s for x in a] == [x.arrival_s for x in c]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    for run in (a, c):
        assert len(run) == traffic.n_requests(tr, 51)
        assert all(0 <= x.arrival_s < 51 for x in run)
        assert all(tr["prompt"]["min"] <= len(x.prompt) <= tr["prompt"]["max"]
                   for x in run)
        assert all(tr["output"]["min"] <= x.max_new <= tr["output"]["max"]
                   for x in run)
        assert all(len(x.prompt) + x.max_new <= tr["max_total"] for x in run)
    # the lengths and gaps are stratified quantiles of the declared
    # distributions, whatever the mix's order
    lens = sorted(len(x.prompt) for x in a)
    assert lens == sorted(traffic._lengths(tr["prompt"], len(a)))


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

def _dims(**kw):
    d = dict(n_layers=2, d_model=8, n_heads=4, n_kv=2, head_dim=2, d_ff=16,
             vocab=10, chunk=4, budget=4, n_queries=2, capacity=16,
             quoka=True)
    d.update(kw)
    return flops.Dims(**d)


def test_flops_against_hand_counts():
    m = _dims()
    # per token per layer: qkv 8*2*(4+4)=128, o 4*2*8=64, mlp 3*8*16=384
    assert flops.linear_flops(m, 3) == 2 * (128 + 64 + 384) * 2 * 3
    assert flops.readout_flops(m, 2) == 2 * 8 * 10 * 2
    # a chunk of 3 valid queries at start 8: selects (16 > 4 + 4);
    # prior = min(budget 4, 8); causal pairs 3*4/2 = 6
    sf, af, sb, ab = flops.attention_row(m, 8, 4, 3)
    assert af == 4 * 4 * 2 * (3 * 4 + 6) * 2
    assert sf == (2 * 2 * 2 * 2 * 8 + 2 * 2 * 2 * 8) * 2
    assert ab == 2 * 2 * (2 * 3 * 4 + 2 * 2 * (4 + 3)) * 2
    assert sb == (2 * 2 * 2 * (8 + 2) + 4 * 2 * 8) * 2
    # the dense branch: a view of 8 keys does not exceed budget + chunk
    dense = _dims(capacity=8)
    sf, af, _, _ = flops.attention_row(dense, 4, 4, 4)
    assert sf == 0 and af == 4 * 4 * 2 * (4 * 4 + 10) * 2
    step = flops.prefill_step(m, [8, 0], [3, 0])     # second row is padding
    assert step["tokens"] == 3
    assert step["flops"] == (flops.linear_flops(m, 3) + flops.readout_flops(m, 1)
                             + flops.attention_row(m, 8, 4, 3)[0]
                             + flops.attention_row(m, 8, 4, 3)[1])
    dec = flops.decode_step(m, [9, 5], [1, 0])
    assert dec["tokens"] == 1
    t, bound = flops.least_time(2e12, 1e9, {"bf16_flops_per_s": 1e12,
                                           "hbm_bytes_per_s": 1e9})
    assert (t, bound) == (2.0, "flops")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _synthetic_trace():
    # device busy [0,10) and [20,30) of a [0,40) window; the host is in a
    # dispatch during the first gap and nowhere during the second
    return {"devices": [{"name": "/device:TPU:0",
                         "ops": [["fusion.1", 0, 6], ["fusion.2", 4, 6],
                                 ["flash_attention_bhtd.3", 20, 10]],
                         "modules": [["jit_prefill_step(1)", 0, 12],
                                     ["jit_decode_step(2)", 19, 12]]}],
            "host": [["bench/serve_loop", 0, 40],
                     ["bench/dispatch_prefill#0", 11, 8],
                     ["engine/prefill_step", 10, 10]]}


def test_trace_reduction_synthetic():
    r = trace_reduce.reduce(_synthetic_trace(), window=(0, 40))
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["idle_pct"] == pytest.approx(50.0)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion": 12e-9, "flash_attention_bhtd": 10e-9})
    # gap [10,20) midpoint 15: innermost open span is the dispatch
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench/dispatch_prefill": 10e-9, "bench/serve_loop": 10e-9})
    r = trace_reduce.reduce({"devices": _synthetic_trace()["devices"],
                             "host": []}, window=(0, 40))
    assert dict(r["idle_gaps"]) == pytest.approx(
        {trace_reduce.OUTSIDE: 20e-9})
    mods = r["modules"]
    assert mods["jit_prefill_step"]["kernel_ns"] == [0]
    assert mods["jit_decode_step"]["kernel_ns"] == [10]


def test_trace_window_ends_at_the_close_mark():
    t = _synthetic_trace()
    assert trace_reduce.window_of(t) == (0, 40)
    t["host"].append([trace_reduce.CLOSE, 25, 0])
    assert trace_reduce.window_of(t) == (0, 25)
    # the drain after the close is not in the window's busy share
    r = trace_reduce.reduce(t)
    assert r["window_s"] == pytest.approx(25e-9)
    assert r["busy_s"] == pytest.approx(15e-9)


def test_trace_reduction_recorded():
    path = BENCH / "testdata" / "trace_v5e_long_prefill.json"
    t = json.loads(path.read_text())
    r = trace_reduce.reduce(t)
    lo, hi = trace_reduce.window_of(t)
    dev = t["devices"][0]
    # busy union by brute force on a 1 us grid
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in dev["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02, abs=2e-5)
    assert 0.0 < r["idle_pct"] < 100.0
    assert sum(v for _, v in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-9
    assert any("prefill_step" in k for k in r["modules"])
    assert any(k for k in r["modules"]
               if any(x > 0 for x in r["modules"][k]["kernel_ns"]))


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------

def test_new_files_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "limits").mkdir()
    (tmp_path / "reference").mkdir()
    (tmp_path / "configs" / "m.json").write_text(
        '{"model_type": "tiny", "hidden_size": 8}')
    (tmp_path / "reference" / "tiny.py").write_text("ARCH = 'tiny'\n")
    (tmp_path / "traffic" / "t.json").write_text('{"rate_per_s": 1}')
    (tmp_path / "limits" / "m.t.json").write_text(
        '{"logit_gap_mean": {"limit": 1}}')
    (tmp_path / "metrics" / "x.y_ms.py").write_text(
        "def read(run):\n    return run * 2\n")
    bm = {"run_seconds": 5,
          "workloads": [{"name": "m.t", "config": "m", "traffic": "t",
                         "chips": 1, "why": "w"}],
          "end_to_end": [{"name": "setup_s", "unit": "s"},
                         {"name": "other", "unit": "s",
                          "workloads": ["elsewhere"]}],
          "per_layer": [{"name": "x.y_ms", "unit": "ms",
                         "workloads": ["m.t"]}]}
    cell = spec.load_cell("m.t", bench_dir=tmp_path, benchmark=bm)
    assert cell.config == {"model_type": "tiny", "hidden_size": 8}
    assert cell.reference.ARCH == "tiny"
    assert cell.traffic == {"rate_per_s": 1}
    assert cell.limits["logit_gap_mean"]["limit"] == 1
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert spec.load_metric_reader("x.y_ms", bench_dir=tmp_path)(21) == 42


_FAKE_REFERENCE = """
import numpy as np

PROGRAM_KEYS = {"sliding_window": "sliding_window"}


def spec_for(config, t_max, n_max, capacity, low=False):
    return low


def logits(params, prompt, served, s):
    lg = np.zeros((len(served), 8))
    lg[:, 0] = 1.0
    return lg
"""


def _arch_bench(tmp_path, model_type, **keys):
    """A bench directory in ``tmp_path`` with one cell, ``m.long_prefill``,
    of qwen3-4b's sizes under ``model_type``, and its BENCHMARK entries."""
    for d in ("configs", "traffic", "reference"):
        (tmp_path / d).mkdir()
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())
    cfg.update(model_type=model_type, **keys)
    (tmp_path / "configs" / "m.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "long_prefill.json").write_text(
        (BENCH / "traffic" / "long_prefill.json").read_text())
    return {"workloads": [{"name": "m.long_prefill", "config": "m",
                           "traffic": "long_prefill", "chips": 1,
                           "why": "w"}],
            "end_to_end": [], "per_layer": []}


def test_a_new_architecture_is_found_and_applied_by_adding_files(tmp_path):
    import run
    import serve
    from types import SimpleNamespace
    bm = _arch_bench(tmp_path, "newarch", sliding_window=96)
    path = tmp_path / "reference" / "newarch.py"
    path.write_text(_FAKE_REFERENCE)
    cell = spec.load_cell("m.long_prefill", bench_dir=tmp_path, benchmark=bm)
    assert cell.reference.__file__ == str(path)
    # its PROGRAM_KEYS reach the program's ModelConfig beside the widths
    mcfg = serve.model_config(cell.config, cell.reference)
    assert mcfg.sliding_window == 96 and mcfg.d_model == 2560
    # and the comparison computes the reference with its maths: token 0
    # is its best, so a served 0 reads 0 below it and any other 1
    sample = [SimpleNamespace(tokens=[5, 6, 7], out=[0, 3, 0])]
    gaps, ctrl = run.compare(None, cell, {"capacity": 64}, sample,
                             control=True)
    assert gaps.tolist() == [0.0, 1.0, 0.0] and ctrl.tolist() == [0.0] * 3


def test_a_program_key_that_model_config_lacks_raises():
    import serve
    from types import SimpleNamespace
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())
    bad = SimpleNamespace(PROGRAM_KEYS={"attention_multiplier":
                                        "attention_multiplier"})
    with pytest.raises(ValueError, match="attention_multiplier"):
        serve.model_config(cfg, bad)


def test_a_model_type_without_a_reference_raises_with_its_path(tmp_path):
    bm = _arch_bench(tmp_path, "nosucharch")
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(tmp_path / "reference"
                                           / "nosucharch.py"))):
        spec.load_cell("m.long_prefill", bench_dir=tmp_path, benchmark=bm)


# ---------------------------------------------------------------------------
# whole runs of a tiny cell on the CPU
# ---------------------------------------------------------------------------

TINY_LIMIT = 0.002      # above the float32 tiny cell (0.0), below control and faults


def _tiny_cell():
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=128,
               vocab_size=256)
    # float32, so that rounding leaves the sound program next to the
    # reference and the control and the faults stand out at this size
    cfg["program"].update(chunk_size=16, budget=32, n_queries=4,
                          keep_first=2, dtype="float32")
    tr = json.loads((BENCH / "traffic" / "long_prefill.json").read_text())
    tr.update(rate_per_s=5.0, prompt={"dist": "log_uniform", "min": 48,
                                      "max": 100},
              output={"dist": "uniform", "min": 6, "max": 12},
              max_total=112, decode_rows=2, max_prefill_tokens=32,
              drain_s=30, warmup={"prompt": 16, "output": 2},
              check={"min_tokens": 30, "max_requests": 8})
    e2e = [m for m in BENCHMARK["end_to_end"]]
    per = [m for m in BENCHMARK["per_layer"]
           if "qwen3-4b.long_prefill" in m.get("workloads",
                                               ["qwen3-4b.long_prefill"])]
    return spec.Cell(name="tiny.long_prefill", config=cfg, traffic=tr,
                     limits={"logit_gap_mean": {"limit": TINY_LIMIT}},
                     chips=1, end_to_end=e2e, per_layer=per)


def _run(fault=None, control=False, trace=False, seed=2 ** 31 + 11,
         rate=None, check=None, **config):
    import run
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    cell = _tiny_cell()
    cell.config.update(config)
    if check:
        cell.traffic["check"] = check
    return run.run_cell(cell, seed, 2.0, trace, peaks=peaks,
                        t_start=time.perf_counter(), backend="xla",
                        fault=fault, control=control, rate=rate)


@pytest.fixture(scope="module")
def sound():
    return _run(control=True)


def test_tiny_cell_is_correct_and_reports_its_metrics(sound):
    line = sound["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                    "output_tokens_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "compared"
    assert sound["diag"]["compiles_in_window"] == 0


def test_control_reads_far_above_the_program(sound):
    prog = float(sound["gaps"].mean())
    ctrl = float(sound["control"].mean())
    assert prog < TINY_LIMIT < ctrl and ctrl >= 3 * prog
    # the control in the program's place fails the run's own comparison
    assert sound["diag"]["control_correct"] is False


def test_untied_readout_is_compared_through_lm_head():
    # the program reads out through params["lm_head"]; a reference that
    # read through emb^T would put other tokens first
    res = _run(seed=17, tie_word_embeddings=False)
    assert res["line"]["correct"] is True
    assert float(res["gaps"].mean()) < TINY_LIMIT


def test_traced_run_reports_per_layer_metrics():
    res = _run(trace=True, seed=3)
    line = res["line"]
    assert line["correct"] is True
    for name in ("sched.queue_wait_p95_ms", "sched.ttft_p50_ms",
                 "engine.prefill_step_ms",
                 "engine.decode_step_ms", "select.kv_fraction",
                 "prefill_step.mfu"):
        assert line["metrics"][name]["value"] > 0, name
    assert 0 < line["metrics"]["select.kv_fraction"]["value"] < 1
    ttft = [t for t in res["diag"]["ttft_ms"] if t is not None]
    assert line["metrics"]["sched.ttft_p50_ms"]["value"] == \
        pytest.approx(float(np.median(ttft)))


@pytest.mark.parametrize("fault", [faults.stale_decode, faults.half_batch,
                                   faults.altered_token,
                                   faults.stale_prefill],
                         ids=["state_unchanged", "half_batch",
                              "altered_token", "prefill_state_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault):
    # rows decode side by side; every finished request is compared
    res = _run(fault=fault, rate=20.0,
               check={"min_tokens": 200, "max_requests": 40})
    assert res["line"]["correct"] is False
    gap = res["line"]["compared"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]


def test_limit_lies_between_the_program_and_the_nearest_failure():
    import control

    def row(v, fault=None, ctrl=None):
        return {"program_gap_mean": v, "fault": fault,
                "control_gap_mean": ctrl}
    rows = [row(0.01), row(0.02, ctrl=0.5), row(0.09, "stale_decode"),
            row(0.05, "half_batch"), row(0.4, "half_batch")]
    got = control.limit_from(rows)
    # half_batch reads under three times the lower: left to another number
    assert got["lower_reading"] == 0.02 and got["control_reading"] == 0.5
    assert got["upper_from"] == "stale_decode"
    assert got["upper_reading"] == 0.09
    assert 0.02 < got["limit"] < 0.09
    assert math.isclose(got["limit"], 0.02 ** (1 / 3) * 0.09 ** (2 / 3))
    assert control.limit_from([row(0.02, ctrl=0.05)])["limit"] is None


def test_run_py_gives_no_result_off_the_chip(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen3-4b.long_prefill", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert proc.returncode == 2
    assert proc.stdout == "" and "no result" in proc.stderr
