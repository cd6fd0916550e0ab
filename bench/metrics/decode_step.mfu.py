"""Share of the chip's bf16 peak that the whole decode step reaches, in %:
the operations its live rows needed (``bench/flops.py``) over the
``engine/decode_step`` span time times the peak.  Moves
``output_tokens_per_s``."""


def read(run):
    recs = run.steps["decode"]
    spans = run.spans("engine/decode_step")
    n = min(len(recs), len(spans))
    if not n:
        return None
    ops = sum(r["flops"] for r in recs[:n])
    return 100.0 * ops / (sum(spans[:n]) * float(run.peaks["bf16_flops_per_s"]))
