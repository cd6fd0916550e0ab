"""One general generator for every traffic mix in ``bench/traffic/``.

A mix is data: length distributions, the arrival process and its rate, the
declared maxima that fix the compiled geometry, and the serving geometry
(decode rows, prefill rows and tokens per step).  Prompt lengths, output
lengths and inter-arrival gaps are drawn at stratified quantiles of the
declared distributions and put in an order that the mix fixes (its
``order_seed``); ``--seed`` draws the token ids.  So every seed serves the
same schedule of the same work: with a dozen long requests in a window,
an order drawn from the seed moved TTFT p95 by half its median from seed
to seed, while two runs of one seed agreed within a few percent.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass
class Arrival:
    prompt: np.ndarray        # (prompt_len,) int32 token ids
    max_new: int
    arrival_s: float          # due time, seconds after the window opens


def rng_for(seed: int, tag: int = 0) -> np.random.Generator:
    """A numpy generator for any whole ``seed`` (also above 2**32)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(dist: Dict, n: int) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    u = _quantiles(n)
    kind = dist["dist"]
    if kind == "log_uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "fixed":
        x = np.full(n, lo, np.float64)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _gaps(kind: str, n: int, seconds: float) -> np.ndarray:
    """n inter-arrival gaps that sum to ``seconds``."""
    u = _quantiles(n)
    if kind == "poisson":
        g = -np.log1p(-u)                      # Exp(1) quantiles
    elif kind == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return g * (seconds / g.sum())


def n_requests(spec: Dict, seconds: float, rate: float = None) -> int:
    rate = float(spec["rate_per_s"] if rate is None else rate)
    return max(1, int(round(rate * seconds)))


def generate(spec: Dict, seed: int, seconds: float, vocab: int,
             rate: float = None) -> List[Arrival]:
    """The open-loop arrivals of one run: ``rate * seconds`` requests due
    in ``[0, seconds)``, sorted by due time."""
    if spec.get("loop", "open") != "open":
        raise ValueError("only open-loop mixes are generated")
    n = n_requests(spec, seconds, rate)
    order = rng_for(int(spec.get("order_seed", 0)), tag=3)
    prompts = order.permutation(_lengths(spec["prompt"], n))
    outs = order.permutation(_lengths(spec["output"], n))
    gaps = order.permutation(_gaps(spec["arrivals"], n, seconds))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    rng = rng_for(seed)
    lo = int(spec.get("token_min", 3))
    return [Arrival(prompt=rng.integers(lo, vocab, int(p)).astype(np.int32),
                    max_new=int(o), arrival_s=float(t))
            for p, o, t in zip(prompts, outs, due)]


def bounds(spec: Dict):
    """(longest prompt, longest output) the mix declares: the compiled
    geometry is sized from these, never from one seed's draw."""
    return int(spec["prompt"]["max"]), int(spec["output"]["max"])


def warmup(spec: Dict, vocab: int) -> List[Arrival]:
    """A short trace inside the declared bounds, distinct from every
    measured one (its own random stream), that drives both step programs."""
    w = spec["warmup"]
    rng = rng_for(0, tag=1)
    return [Arrival(prompt=rng.integers(3, vocab, int(w["prompt"]))
                    .astype(np.int32), max_new=int(w["output"]),
                    arrival_s=0.0) for _ in range(int(w.get("requests", 1)))]
