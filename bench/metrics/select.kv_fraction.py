"""Mean selected-KV fraction of the prefill steps' layers: selected keys
over the prior context, from the program's in-jit per-layer counters
(``select/prefill/kv_fraction``), read on the replay of the window's
prompts that follows a traced run.  Moves ``ttft_p95_ms``."""


def read(run):
    h = run.histogram("select/prefill/kv_fraction")
    if h is None or not h.count:
        return None
    return h.mean
