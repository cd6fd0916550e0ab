"""95th percentile of a request's service before its first token, in ms:
the program's ``request/prefill`` spans (admission to first token, so
TTFT less the queue wait), every one recorded in the window.  Moves
``ttft_p95_ms``."""


def read(run):
    import numpy as np
    d = run.spans("request/prefill")
    if not d:
        return None
    return float(np.percentile(np.asarray(d, np.float64), 95)) * 1e3
