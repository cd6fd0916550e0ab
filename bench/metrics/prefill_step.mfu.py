"""Share of the chip's bf16 peak that the whole chunk-prefill step reaches,
in %: the operations its rows needed (``bench/flops.py``, from the tokens
each row carried) over the ``engine/prefill_step`` span time times the
peak.  Moves ``ttft_p95_ms``."""


def read(run):
    recs = run.steps["prefill"]
    spans = run.spans("engine/prefill_step")
    n = min(len(recs), len(spans))
    if not n:
        return None
    ops = sum(r["flops"] for r in recs[:n])
    return 100.0 * ops / (sum(spans[:n]) * float(run.peaks["bf16_flops_per_s"]))
