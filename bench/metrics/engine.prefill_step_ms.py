"""Mean time of the engine's chunk-prefill steps, in ms: the program's
``engine/prefill_step`` spans (dispatch through the blocking token fetch,
so device time included), every one of the window's, not a reservoir.
Moves ``ttft_p95_ms``."""


def read(run):
    d = run.spans("engine/prefill_step")
    return sum(d) / len(d) * 1e3 if d else None
