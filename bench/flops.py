"""Operations and bytes that a step's work needs, from the algorithm's
shapes and the tokens each row of the step actually carried.

The counts are the same whatever kernel implements the work: padding rows,
padded key slots and recomputation are not counted, so a share of a peak
or roofline built on them cannot pass 100% unless the time leaves work out.
A multiply-add counts as 2 operations.  Bytes count each operand read once
from HBM and each result written once, in the served dtype (bf16, 2
bytes), with float32 scores (4 bytes).
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    chunk: int
    budget: int
    n_queries: int
    capacity: int           # the program's cache view, tokens per row
    quoka: bool

    @classmethod
    def from_config(cls, config: dict, capacity: int) -> "Dims":
        prog = config["program"]
        d = int(config["hidden_size"])
        h = int(config["num_attention_heads"])
        return cls(n_layers=int(config["num_hidden_layers"]), d_model=d,
                   n_heads=h, n_kv=int(config["num_key_value_heads"]),
                   head_dim=int(config.get("head_dim") or d // h),
                   d_ff=int(config["intermediate_size"]),
                   vocab=int(config["vocab_size"]),
                   chunk=int(prog["chunk_size"]),
                   budget=min(int(prog["budget"]), capacity),
                   n_queries=int(prog["n_queries"]), capacity=capacity,
                   quoka=prog["method"] == "quoka")

    def selects(self, width: int) -> bool:
        """The program selects when its view holds more than budget +
        width keys (else it takes the dense branch)."""
        return self.quoka and self.capacity > self.budget + width


def linear_flops(m: Dims, tokens: int) -> float:
    """Projections and MLP of every layer for ``tokens`` tokens."""
    hd = m.head_dim
    per_tok = (m.d_model * hd * (m.n_heads + 2 * m.n_kv)
               + m.n_heads * hd * m.d_model + 3 * m.d_model * m.d_ff)
    return 2.0 * per_tok * m.n_layers * tokens


def readout_flops(m: Dims, rows: int) -> float:
    """One token's logits per row (the last valid position)."""
    return 2.0 * m.d_model * m.vocab * rows


def attention_row(m: Dims, start: int, width: int, valid: int):
    """(scoring flops, attention flops, scoring bytes, attention bytes) of
    one row, all layers: ``valid`` queries of a unit ``width`` wide at
    ``start``.  Scoring: the kept queries against every prior key;
    attention: each query against the selected prior keys and its own
    unit up to itself."""
    if valid <= 0:
        return 0.0, 0.0, 0.0, 0.0
    hd, kv, h = m.head_dim, m.n_kv, m.n_heads
    sel = m.selects(width)
    prior = min(m.budget, start) if sel else start
    causal = valid * (valid + 1) / 2.0
    att_f = 4.0 * h * hd * (valid * prior + causal)
    att_b = (BF16 * hd * (2 * valid * h + 2 * kv * (prior + valid)))
    if sel and start > 0:
        nq = min(m.n_queries, valid)
        sc_f = 2.0 * nq * kv * hd * start + 2.0 * kv * hd * start
        sc_b = BF16 * kv * hd * (start + nq) + F32 * kv * start
    else:
        sc_f = sc_b = 0.0
    n = m.n_layers
    return sc_f * n, att_f * n, sc_b * n, att_b * n


def prefill_step(m: Dims, starts, valid_lens) -> dict:
    """Counts of one chunk-prefill step; rows with no valid token are
    padding and count nothing."""
    out = dict(flops=0.0, attn_flops=0.0, attn_bytes=0.0, tokens=0)
    rows = 0
    for s, v in zip(starts, valid_lens):
        s, v = int(s), int(v)
        if v <= 0:
            continue
        rows += 1
        sf, af, sb, ab = attention_row(m, s, m.chunk, v)
        out["attn_flops"] += sf + af
        out["attn_bytes"] += sb + ab
        out["flops"] += linear_flops(m, v) + sf + af
        out["tokens"] += v
    out["flops"] += readout_flops(m, rows)
    return out


def decode_step(m: Dims, positions, live) -> dict:
    """Counts of one decode step: each live row decodes one token at its
    position."""
    out = dict(flops=0.0, attn_flops=0.0, attn_bytes=0.0, tokens=0)
    for p, l in zip(positions, live):
        if not int(l):
            continue
        sf, af, sb, ab = attention_row(m, int(p), 1, 1)
        out["attn_flops"] += sf + af
        out["attn_bytes"] += sb + ab
        out["flops"] += linear_flops(m, 1) + sf + af + readout_flops(m, 1)
        out["tokens"] += 1
    return out


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) the chip needs at least: the larger of operations
    over peak FLOP/s and bytes over peak bandwidth, and which one it is."""
    tf = flops / float(peaks["bf16_flops_per_s"])
    tb = nbytes / float(peaks["hbm_bytes_per_s"])
    return (tf, "flops") if tf >= tb else (tb, "bytes")
