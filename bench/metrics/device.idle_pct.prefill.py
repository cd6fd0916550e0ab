"""Share of the traced window in which no operation ran on the device, in
%: 1 - busy union / window, from the profiler trace of a steady stretch
of a long-prompt cell.  Moves ``ttft_p95_ms``."""


def read(run):
    red = run.trace
    return None if red is None else red["idle_pct"]
