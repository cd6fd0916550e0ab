"""Finds a cell's pieces by name.

``BENCHMARK.json`` names each cell ``<config>.<traffic>``; everything that
belongs to one configuration, one traffic mix, one cell's limits or one
per-layer metric lives in a file of its own, found here by that name:

    bench/configs/<config>.json     sizes as run, source, cuts, departures
    bench/reference/<model_type>.py the plain reference of the config's
                                    ``model_type``
    bench/traffic/<traffic>.json    lengths, arrivals, rate, serving geometry
    bench/limits/<cell>.json        limits of the numbers that decide correct
    bench/metrics/<metric>.py       one reader per per-layer metric

A later cell, configuration, mix or metric is added by adding files and
entries; no file here needs an edit.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    limits: Dict
    chips: int
    end_to_end: List[Dict]          # the cell's end-to-end metric entries
    per_layer: List[Dict]           # the cell's per-layer metric entries
    bench_dir: Path = BENCH_DIR     # where its files are found

    @property
    def reference(self):
        """The plain reference module of the configuration's
        ``model_type``."""
        return load_reference(self.config["model_type"], self.bench_dir)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              benchmark: Optional[Dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files read."""
    if benchmark is None:
        benchmark = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = load_json(bench_dir / "configs" / f"{w['config']}.json")
    if "model_type" not in cfg:
        raise KeyError(f"configs/{w['config']}.json names no model_type")
    load_reference(cfg["model_type"], bench_dir)      # raises where none
    tr = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    lim_path = bench_dir / "limits" / f"{name}.json"
    limits = load_json(lim_path) if lim_path.exists() else {}
    e2e = [m for m in benchmark["end_to_end"] if _applies(m, name)]
    per = [m for m in benchmark["per_layer"] if _applies(m, name)]
    return Cell(name=name, config=cfg, traffic=tr, limits=limits,
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per,
                bench_dir=bench_dir)


@functools.cache
def load_reference(model_type: str, bench_dir: Path = BENCH_DIR):
    """The module ``bench/reference/<model_type>.py``, loaded once: a
    configuration is compared only against its own architecture's
    maths."""
    path = bench_dir / "reference" / f"{model_type}.py"
    if not path.exists():
        raise FileNotFoundError(f"model_type {model_type!r} has no plain "
                                f"reference at {path}")
    name = f"bench_reference_{model_type}"
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    # a dataclass resolves its annotations through sys.modules
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def load_metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader "
                                f"at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
