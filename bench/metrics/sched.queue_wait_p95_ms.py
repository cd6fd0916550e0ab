"""95th percentile of the scheduler's admission wait, in ms: from each
request's due time to the engine step that admitted it, on the engine's
clock, every admission of the window.  Moves ``ttft_p95_ms``."""


def read(run):
    import numpy as np
    w = run.admission_waits
    if not w:
        return None
    return float(np.percentile(np.asarray(w, np.float64), 95)) * 1e3
