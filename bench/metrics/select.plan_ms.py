"""Device time of QUOKA's selection in a chunk-prefill step, in ms: the
mean, over the traced ``prefill_step`` module runs, of the time of ops
under ``plan_scores``, ``plan_topk`` or ``plan_materialize``
(``bench/scopes.py``), the scoring kernel included.  Moves
``ttft_p95_ms``."""
import scopes


def read(run):
    got = scopes.of_run(run)
    return None if got is None else scopes.ms_under(
        got["tables"], "prefill_step",
        ("plan_scores", "plan_topk", "plan_materialize"))
