"""Share of the roofline that the attention kernels reach in the traced
prefill steps, in %: the least time the chip needs for the scoring and
attention work those steps' rows carried (``bench/flops.py``: the larger
of operations over peak FLOP/s and bytes over peak bandwidth) over the
device time of the Pallas kernels (QUOKA scoring and flash attention
together) inside those steps' module runs.  Each module run is matched to
the harness's ``bench/dispatch_prefill#<i>`` annotation that dispatched it.
Moves ``ttft_p95_ms``."""
import bisect

import flops


def read(run):
    red = run.trace
    if red is None:
        return None
    disp = sorted((s, int(n.rsplit("#", 1)[1])) for n, s, _ in red["host"]
                  if n.startswith("bench/dispatch_prefill#"))
    if not disp:
        return None
    starts = [s for s, _ in disp]
    recs = run.steps["prefill"]
    kern = least = 0.0
    bounds = {}
    for name, m in red["modules"].items():
        if "prefill_step" not in name:
            continue
        for (ms, _), kns in zip(m["runs"], m["kernel_ns"]):
            j = bisect.bisect_right(starts, ms) - 1
            if j < 0 or kns <= 0:
                continue
            rec = recs[disp[j][1]]
            t, bound = flops.least_time(rec["attn_flops"], rec["attn_bytes"],
                                        run.peaks)
            kern += kns * 1e-9
            least += t
            bounds[bound] = bounds.get(bound, 0) + 1
    if kern <= 0:
        return None
    run.notes["attention_roofline_bound"] = bounds
    return 100.0 * least / kern
