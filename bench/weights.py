"""Random weights for a served configuration, made by the benchmark.

The weights are drawn on the device in one jitted call from ``--seed``, in
the type they are served in, laid out as the program's parameter tree
(its shapes are read with ``jax.eval_shape``, nothing is computed by the
program).  The program and the plain reference both read these arrays, so
the reference takes nothing that the program made.

Each leaf follows the scale of the program's own initialiser: norm gains
1, biases 0, embeddings N(0, 0.02), projections N(0, 1/d_in), and the
attention output N(0, 1/(n_heads * head_dim * 2 * n_layers)).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_data(seed: int, tag: int = 0) -> np.ndarray:
    """Threefry key words for any whole ``seed`` (also above 2**32)."""
    return np.random.SeedSequence([int(seed), tag]).generate_state(
        2, np.uint32)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _in_wo(path) -> bool:
    return any(getattr(p, "key", None) == "wo" for p in path)


def make(model, seed: int, dtype) -> dict:
    """The parameter tree of ``model`` in ``dtype``, drawn from ``seed``."""
    cfg = model.cfg
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    wo_std = 1.0 / math.sqrt(cfg.n_heads * cfg.resolved_head_dim * 2
                             * cfg.n_layers)

    def leaf(path, sd, key):
        name, shape = _leaf_name(path), sd.shape
        if name == "g":
            return jnp.ones(shape, dtype)
        if name == "b":
            return jnp.zeros(shape, dtype)
        if name == "emb":
            std = 0.02
        elif name == "w":
            std = wo_std if _in_wo(path) else 1.0 / math.sqrt(shape[-2])
        else:
            raise ValueError(f"no initialisation rule for parameter "
                             f"{jax.tree_util.keystr(path)}")
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    @jax.jit
    def draw(words):
        keys = jax.random.split(jax.random.wrap_key_data(words), len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(p, sd, keys[i]) for i, (p, sd) in enumerate(flat)])

    return draw(jnp.asarray(key_data(seed)))
