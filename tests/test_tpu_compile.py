"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode cannot say whether Mosaic accepts a kernel's block shapes;
the TPU compiler can, for a described chip that is not attached.  These
compile the kernels the quoka serving path runs, at qwen3-4b's widths
(32 query / 8 KV heads, head_dim 128, chunk 128, budget 1024), in bf16:

  * flash attention over a prefill chunk's ``[budget | chunk]`` keys,
  * flash attention for a decode step (one query over ``budget + 1``),
  * QUOKA scoring over a 4224-key context (prefill's 16 and decode's 1
    pre-aggregated queries),

and pin the outcome for the fused selected-attention kernel: Mosaic
rejects its tiling, so ``Engine`` refuses it on the compiled backend.

The topology is described inside a fixture (never at import: only one
process may load the TPU library), and the persistent compilation cache
is off around these compiles (their entries cannot be read back without
a chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_bhtd
from repro.kernels.quoka_score import quoka_score_bhtd
from repro.kernels.selected_attention import selected_attention_bhtd

H, N_KV, D, CHUNK, BUDGET, T = 32, 8, 128, 128, 1024, 4224


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(t):
    tk = BUDGET + t
    return (lambda q, k, v, ok: flash_attention_bhtd(
        q, k, v, ok, causal=True, boundary=BUDGET, interpret=False),
        [(2, H, t, D), (2, N_KV, tk, D), (2, N_KV, tk, D),
         ((2, N_KV, tk), jnp.bool_)])


def _score(n_q):
    return (lambda qb, k, ok: quoka_score_bhtd(qb, k, ok, interpret=False),
            [((2, N_KV, n_q, D), jnp.float32), (2, N_KV, T, D),
             ((2, T), jnp.bool_)])


CASES = {
    "flash_prefill_chunk": _flash(CHUNK),
    "flash_decode": _flash(1),
    "score_prefill": _score(16),
    "score_decode": _score(1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(one_chip, case):
    fn, specs = CASES[case]
    args = [_shape(one_chip, *s) if isinstance(s[0], tuple)
            else _shape(one_chip, s) for s in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_selected_attention_refused_on_compiled_backend(one_chip):
    """Mosaic rejects the fused kernel's (1, g) key-position blocks at
    g = 16, so an engine asked for it on ``pallas`` refuses up front
    instead of falling back to interpret mode or the XLA oracle."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serving.engine import Engine

    g = 16
    args = [_shape(one_chip, (1, H, CHUNK, D)), _shape(one_chip, (1, N_KV, T, D)),
            _shape(one_chip, (1, N_KV, T, D)),
            _shape(one_chip, (1, T), jnp.int32),
            _shape(one_chip, (1, BUDGET // g), jnp.int32),
            _shape(one_chip, (1,), jnp.int32)]
    fused = jax.jit(lambda *a: selected_attention_bhtd(
        *a, granularity=g, interpret=False))
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        fused.lower(*args).compile()

    cfg = get_config("qwen3-4b").smoke()
    cfg = dataclasses.replace(cfg, quoka=dataclasses.replace(
        cfg.quoka, granularity=g, fused_select_attn=True))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="Mosaic"):
        Engine(model, params, method="quoka", backend="pallas")
    assert Engine(model, params, method="quoka",
                  backend="pallas_interpret").fused
