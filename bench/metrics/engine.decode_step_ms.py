"""Mean time of the engine's decode steps, in ms: the program's
``engine/decode_step`` spans, every one of the window's.  Moves
``itl_p95_ms``."""


def read(run):
    d = run.spans("engine/decode_step")
    return sum(d) / len(d) * 1e3 if d else None
