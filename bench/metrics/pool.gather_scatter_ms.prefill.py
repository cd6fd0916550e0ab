"""Device time of the paged pool's gather and scatter in a chunk-prefill
step, in ms: the mean, over the traced ``prefill_step`` module runs, of
the time of ops under the ``pool_gather`` or ``pool_scatter`` scope
(``bench/scopes.py``).  Moves ``ttft_p95_ms``."""
import scopes


def read(run):
    got = scopes.of_run(run)
    return None if got is None else scopes.ms_under(
        got["tables"], "prefill_step", ("pool_gather", "pool_scatter"))
