"""XLA vs Pallas(interpret) backend parity across the whole selection +
post-selection-attention path (the tentpole contract of the kernel facade:
every backend produces the same numbers within tolerance).

Shapes are deliberately GQA and RAGGED (T, budget and chunk sizes that are
not multiples of the kernel block sizes) so the kernel's internal padding
and per-KV-head `k_valid` handling are exercised.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import QuokaConfig
from repro.core import selection as sel_mod
from repro.core.attention import dense_attention
from repro.core.chunked_prefill import chunked_sparse_attention
from repro.core.quoka import quoka_scores, subselect_queries
from repro.kernels import ops as kops
from repro.models.model import build_model

@pytest.fixture(autouse=True)
def _no_env_backend(monkeypatch):
    """An exported REPRO_BACKEND outranks cfg.backend and would make every
    cfg-driven comparison here vacuous (same backend on both sides)."""
    monkeypatch.delenv("REPRO_BACKEND", raising=False)


KEY = jax.random.PRNGKey(11)
# ragged GQA geometry: T=192 (3 chunks of 64), budget 40, none of them
# multiples of the kernel's 128-lane blocks
B, T, H, NKV, D = 2, 192, 4, 2, 16
CHUNK, BUDGET = 64, 40


def _qkv(key=KEY, t=T):
    q = jax.random.normal(key, (B, t, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, t, NKV, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, t, NKV, D))
    return q, k, v


def _cfg(backend, **kw):
    base = dict(chunk_size=CHUNK, budget=BUDGET, n_queries=8, keep_first=2)
    base.update(kw)
    return QuokaConfig(backend=backend, **base)


# ---------------------------------------------------------------------------
# facade-level: boundary-prefix mask semantics vs dense_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("boundary,tq", [(40, 48), (13, 7), (0, 33)])
def test_attention_boundary_matches_dense_mask_semantics(boundary, tq):
    """ops.attention's [prefix | causal chunk] boundary mask must equal the
    legacy ad-hoc pattern: concat([k_valid prefix mask, tril], axis=-1)
    fed to dense_attention."""
    tk = boundary + tq
    q = jax.random.normal(KEY, (B, tq, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, tk, NKV, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, tk, NKV, D))
    prefix_ok = jax.random.bernoulli(jax.random.fold_in(KEY, 3), 0.7,
                                     (B, NKV, boundary))
    k_valid = jnp.concatenate([prefix_ok, jnp.ones((B, NKV, tq), bool)], -1)

    m_sel = jnp.broadcast_to(prefix_ok[:, :, None, :],
                             (B, NKV, tq, boundary))
    tri = jnp.broadcast_to(jnp.tril(jnp.ones((tq, tq), bool))[None, None],
                           (B, NKV, tq, tq))
    mask = jnp.concatenate([m_sel, tri], axis=-1)
    want = dense_attention(q, k, v, mask)

    for backend in ("xla", "pallas_interpret"):
        got = kops.attention(q, k, v, k_valid, causal=True,
                             boundary=boundary, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4,
                                   err_msg=f"backend={backend}")


def test_attention_backends_match_on_shared_valid():
    """(b, tk) shared k_valid keeps working (pre-facade call signature)."""
    q, k, v = _qkv(t=96)
    valid = jax.random.bernoulli(jax.random.fold_in(KEY, 9), 0.8, (B, 96))
    a = kops.attention(q, k, v, valid, causal=True, backend="xla")
    b_ = kops.attention(q, k, v, valid, causal=True,
                        backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               atol=2e-5, rtol=1e-4)


def test_resolve_backend_priority(monkeypatch):
    cfg = QuokaConfig(backend="pallas_interpret")
    assert kops.resolve_backend("xla", cfg) == "xla"          # arg wins
    assert kops.resolve_backend(None, cfg) == "pallas_interpret"
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    assert kops.resolve_backend(None, cfg) == "xla"           # env beats cfg
    monkeypatch.delenv("REPRO_BACKEND")
    assert kops.resolve_backend(None, None) in kops.BACKENDS  # hardware auto
    with pytest.raises(ValueError):
        kops.resolve_backend("cuda", None)


# ---------------------------------------------------------------------------
# scoring parity
# ---------------------------------------------------------------------------

def test_quoka_scores_backend_parity():
    q, k, _ = _qkv()
    qs = subselect_queries(q, 8, n_kv=NKV)
    valid = jnp.arange(T)[None].repeat(B, 0) < 100            # ragged valid
    s_x = quoka_scores(qs, k, valid, _cfg("xla"))
    s_p = quoka_scores(qs, k, valid, _cfg("pallas_interpret"))
    assert s_p.shape == (B, NKV, T)
    np.testing.assert_allclose(np.asarray(s_x), np.asarray(s_p),
                               atol=1e-4, rtol=1e-4)


def test_quoka_scores_ablation_arms_fall_back():
    """"dot"/"mean" ablations are outside the kernel contract: the kernel
    backend must silently use the einsum path, not crash or mis-score."""
    q, k, _ = _qkv()
    qs = subselect_queries(q, 8, n_kv=NKV)
    valid = jnp.ones((B, T), bool)
    for kw in (dict(scoring="dot"), dict(query_agg="mean")):
        s_x = quoka_scores(qs, k, valid, _cfg("xla", **kw))
        s_p = quoka_scores(qs, k, valid, _cfg("pallas_interpret", **kw))
        np.testing.assert_allclose(np.asarray(s_x), np.asarray(s_p),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# chunked prefill parity — every selection method
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method",
                         [m for m in sel_mod.METHODS if m != "full"])
def test_chunked_sparse_attention_backend_parity(method):
    q, k, v = _qkv()
    out_x = chunked_sparse_attention(q, k, v, _cfg("xla"), method)
    out_p = chunked_sparse_attention(q, k, v, _cfg("pallas_interpret"),
                                     method)
    assert out_p.shape == (B, T, H, D)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_p),
                               atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# engine / model parity — the serving path really runs the kernels
# ---------------------------------------------------------------------------

def _smoke_model(arch="qwen3-4b", **q_over):
    cfg = get_config(arch).smoke(n_layers=2, d_model=64, n_heads=4,
                                 n_kv_heads=2, d_ff=128, vocab=128)
    qk = dict(chunk_size=16, budget=24, n_queries=4, keep_first=2)
    qk.update(q_over)
    cfg = dataclasses.replace(cfg, quoka=dataclasses.replace(cfg.quoka, **qk))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


def test_model_prefill_backend_parity_and_kernel_use(monkeypatch):
    """model.prefill(backend="pallas_interpret") matches the XLA path AND
    traces through flash_attention_bhtd/quoka_score_bhtd (not the dense
    fallback)."""
    calls = {"attn": 0, "score": 0}
    real_fa, real_qs = kops.flash_attention_bhtd, kops.quoka_score_bhtd
    monkeypatch.setattr(
        kops, "flash_attention_bhtd",
        lambda *a, **k: (calls.__setitem__("attn", calls["attn"] + 1),
                         real_fa(*a, **k))[1])
    monkeypatch.setattr(
        kops, "quoka_score_bhtd",
        lambda *a, **k: (calls.__setitem__("score", calls["score"] + 1),
                         real_qs(*a, **k))[1])

    model, params, cfg = _smoke_model()
    toks = jnp.asarray(
        np.random.default_rng(0).integers(3, cfg.vocab, (2, 64)), jnp.int32)
    cache = model.init_cache(2, 80)
    lx, _ = model.prefill(params, {"tokens": toks}, cache, "quoka",
                          backend="xla")
    assert calls == {"attn": 0, "score": 0}
    cache = model.init_cache(2, 80)
    lp, _ = model.prefill(params, {"tokens": toks}, cache, "quoka",
                          backend="pallas_interpret")
    assert calls["attn"] > 0 and calls["score"] > 0
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               atol=5e-4, rtol=5e-3)


@pytest.mark.parametrize("env,backend,kernels", [
    ("pallas_interpret", "xla", False), ("xla", "pallas_interpret", True)])
def test_model_backend_outranks_env(monkeypatch, env, backend, kernels):
    """An exported REPRO_BACKEND cannot switch a model call's backend, QUOKA
    scoring included: the kernels run exactly when the call asks for them."""
    calls = []
    for name in ("flash_attention_bhtd", "quoka_score_bhtd"):
        real = getattr(kops, name)
        monkeypatch.setattr(kops, name, lambda *a, _r=real, _n=name, **k: (
            calls.append(_n), _r(*a, **k))[1])
    monkeypatch.setenv("REPRO_BACKEND", env)
    model, params, cfg = _smoke_model()
    toks = jnp.asarray(
        np.random.default_rng(0).integers(3, cfg.vocab, (2, 64)), jnp.int32)
    model.prefill(params, {"tokens": toks}, model.init_cache(2, 80), "quoka",
                  backend=backend)
    assert set(calls) == ({"flash_attention_bhtd", "quoka_score_bhtd"}
                          if kernels else set())


def test_engine_generate_backend_parity():
    from repro.serving.engine import Engine
    from repro.serving.sampler import SamplerConfig
    model, params, cfg = _smoke_model()
    toks = jnp.asarray(
        np.random.default_rng(1).integers(3, cfg.vocab, (2, 48)), jnp.int32)
    outs = {}
    for be in ("xla", "pallas_interpret"):
        eng = Engine(model, params, method="quoka", backend=be,
                     sampler=SamplerConfig(temperature=0.0))
        assert eng.backend == be
        r = eng.generate({"tokens": toks}, 3, key=jax.random.PRNGKey(5))
        assert r.backend == be
        outs[be] = r.tokens
    # greedy sampling: identical numerics within tolerance -> same tokens
    assert (outs["xla"] == outs["pallas_interpret"]).all()


def test_engine_generate_returns_first_token_logits():
    """``generate`` returns the prefill's logits, equal to a direct
    ``Model.prefill``, and the greedy first token is their argmax."""
    from repro.serving.engine import Engine
    model, params, cfg = _smoke_model()
    toks = jnp.asarray(
        np.random.default_rng(3).integers(3, cfg.vocab, (2, 48)), jnp.int32)
    eng = Engine(model, params, method="quoka", backend="xla")
    r = eng.generate({"tokens": toks}, 2)
    want, _ = model.prefill(params, {"tokens": toks}, model.init_cache(2, 50),
                            "quoka", backend="xla")
    assert r.logits.shape == (2, cfg.vocab)
    np.testing.assert_allclose(np.asarray(r.logits), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(r.tokens[:, 0],
                                  np.argmax(np.asarray(r.logits), -1))


def test_mla_prefill_backend_parity():
    """MLA's latent-space selected attention (zero-padded V trick) agrees
    across backends."""
    model, params, cfg = _smoke_model("deepseek-v3-671b")
    toks = jnp.asarray(
        np.random.default_rng(2).integers(3, cfg.vocab, (1, 64)), jnp.int32)
    cache = model.init_cache(1, 80)
    lx, _ = model.prefill(params, {"tokens": toks}, cache, "quoka",
                          backend="xla")
    cache = model.init_cache(1, 80)
    lp, _ = model.prefill(params, {"tokens": toks}, cache, "quoka",
                          backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               atol=5e-4, rtol=5e-3)
