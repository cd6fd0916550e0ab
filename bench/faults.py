"""Faults planted in the timed path underneath a run, to see ``correct``
come out false (``bench/tests``) and to read the compared numbers under
them on the chip (``bench/control.py --fault <name>``).  Each takes the
serve state and wraps its two compiled step programs."""
from __future__ import annotations

import numpy as np


def stale_decode(state):
    """The decode step returns the pool it was given: no decoded KV is
    written."""
    pf, dc = state.fns

    def decode(p, data, *a):
        out = dc(p, data, *a)
        return (data,) + tuple(out[1:])
    state.fns = (pf, decode)


def stale_prefill(state):
    """The prefill step returns the pool it was given: no prompt KV is
    written."""
    pf, dc = state.fns

    def prefill(p, data, *a):
        out = pf(p, data, *a)
        return (data,) + tuple(out[1:])
    state.fns = (prefill, dc)


def half_batch(state):
    """The decode step leaves the second half of its rows out: their KV is
    not written and they emit the first half's tokens."""
    pf, dc = state.fns

    def decode(p, data, table, tokens, pos, live, key):
        h = len(live) // 2
        live = np.array(live)
        live[h:] = 0
        out = dc(p, data, table, tokens, pos, live, key)
        tok = out[1].at[h:].set(out[1][:len(live) - h])
        return (out[0], tok) + tuple(out[2:])
    state.fns = (pf, decode)


def altered_token(state):
    """Each decode step's first row emits token 3, or 4 where it drew 3."""
    import jax.numpy as jnp
    pf, dc = state.fns

    def decode(*a):
        out = dc(*a)
        tok = out[1].at[0].set(jnp.where(out[1][0] == 3, 4, 3))
        return (out[0], tok) + tuple(out[2:])
    state.fns = (pf, decode)


FAULTS = {f.__name__: f for f in (stale_decode, stale_prefill, half_batch,
                                  altered_token)}
