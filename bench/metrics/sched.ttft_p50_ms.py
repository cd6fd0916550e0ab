"""Median TTFT, in ms: from each request's due time to its first token,
every request due in the window, as ``ttft_p50_ms`` would read it.  Per
layer, not end to end: TTFT is made of whole engine steps and a median
of a dozen requests moves by steps from run to run.  Moves
``ttft_p95_ms``."""


def read(run):
    import numpy as np
    if not run.ttft:
        return None
    return float(np.median(np.asarray(run.ttft, np.float64))) * 1e3
