"""Share of the traced window in which no operation ran on the device, in
%: 1 - busy union / window, from the profiler trace of a steady stretch
of a decode-bound cell.  Moves ``output_tokens_per_s``."""


def read(run):
    red = run.trace
    return None if red is None else red["idle_pct"]
