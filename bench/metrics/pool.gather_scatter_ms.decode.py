"""Device time of the paged pool's gather and scatter in a decode step,
in ms: the mean, over the traced ``decode_step`` module runs, of the time
of ops under the ``pool_gather`` or ``pool_scatter`` scope
(``bench/scopes.py``).  Moves ``itl_p95_ms``."""
import scopes


def read(run):
    got = scopes.of_run(run)
    return None if got is None else scopes.ms_under(
        got["tables"], "decode_step", ("pool_gather", "pool_scatter"))
