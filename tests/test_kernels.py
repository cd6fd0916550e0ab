"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhtd
from repro.kernels.ops import flash_attention, quoka_score
from repro.kernels.quoka_score import quoka_score_bhtd

KEY = jax.random.PRNGKey(0)

FLASH_CASES = [
    # (b, h, h_kv, tq, tk, d, causal, boundary)
    (1, 4, 2, 128, 256, 64, True, 0),
    (2, 8, 8, 64, 192, 32, True, 64),
    (1, 2, 1, 37, 119, 80, True, 16),       # ragged
    (1, 4, 4, 16, 300, 64, False, 0),       # cross attention
    (1, 1, 1, 8, 8, 8, True, 0),            # tiny
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_matches_ref(case, dtype):
    b, h, hkv, tq, tk, d, causal, boundary = case
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, h, tq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (b, hkv, tk, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (b, hkv, tk, d), dtype)
    valid = jax.random.bernoulli(jax.random.fold_in(KEY, 4), 0.9, (b, tk))
    out = flash_attention_bhtd(q, k, v, valid, causal=causal,
                               boundary=boundary, block_q=32, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal,
                                   boundary=boundary, k_valid=valid)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_all_keys_invalid_rows_zero():
    b, h, tq, tk, d = 1, 2, 16, 64, 32
    q = jax.random.normal(KEY, (b, h, tq, d))
    k = jax.random.normal(KEY, (b, h, tk, d))
    v = jax.random.normal(KEY, (b, h, tk, d))
    valid = jnp.zeros((b, tk), bool)
    out = flash_attention_bhtd(q, k, v, valid, causal=False, block_q=16,
                               block_k=32)
    assert float(jnp.abs(out).max()) == 0.0


SCORE_CASES = [
    (2, 4, 16, 512, 64),
    (1, 1, 16, 300, 576),     # MLA-latent-like single-kv-head
    (2, 2, 5, 100, 80),
    (1, 8, 1, 128, 128),      # single query (decode)
]


@pytest.mark.parametrize("case", SCORE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quoka_score_kernel_matches_ref(case, dtype):
    b, nkv, nq, t, d = case
    qb = jax.random.normal(jax.random.fold_in(KEY, 5), (b, nkv, nq, d), dtype)
    qb = qb / jnp.linalg.norm(qb.astype(jnp.float32), axis=-1,
                              keepdims=True).astype(dtype)
    kk = jax.random.normal(jax.random.fold_in(KEY, 6), (b, nkv, t, d), dtype)
    valid = jax.random.bernoulli(jax.random.fold_in(KEY, 7), 0.8, (b, t))
    out = quoka_score_bhtd(qb, kk, valid, block_t=128)
    want = ref.quoka_score_ref(qb, kk, valid)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=tol, rtol=tol)


def test_ops_wrappers_layouts():
    """ops.py converts BTHD <-> BHTD correctly on both backends."""
    b, t, h, hkv, d = 1, 64, 4, 2, 32
    q = jax.random.normal(KEY, (b, t, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, hkv, d))
    o_xla = flash_attention(q, k, v, backend="xla")
    o_pl = flash_attention(q, k, v, backend="pallas_interpret")
    assert o_xla.shape == (b, t, h, d)
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_pl),
                               atol=2e-5, rtol=1e-4)

    qb = jax.random.normal(KEY, (b, 8, hkv, d))
    qb = qb / jnp.linalg.norm(qb, axis=-1, keepdims=True)
    valid = jnp.ones((b, t), bool)
    s_xla = quoka_score(qb, k, valid, backend="xla")
    s_pl = quoka_score(qb, k, valid, backend="pallas_interpret")
    assert s_xla.shape == (b, hkv, t)
    np.testing.assert_allclose(np.asarray(s_xla), np.asarray(s_pl),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# satellite: fully-masked query rows must yield zeros, never NaN/Inf
# ---------------------------------------------------------------------------

def test_flash_midstream_all_invalid_block_per_kv_head():
    """One key block fully invalid for one KV head, mid-stream, under the
    causal [boundary | chunk] mask: every output must stay finite and match
    the oracle (the online-softmax l==0 guard)."""
    b, h, hkv, tq, tk, d = 1, 4, 2, 32, 128, 16
    q = jax.random.normal(jax.random.fold_in(KEY, 11), (b, h, tq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 12), (b, hkv, tk, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 13), (b, hkv, tk, d))
    valid = np.ones((b, hkv, tk), bool)
    valid[:, 0, 32:64] = False          # kv-head 0: key block 1 fully masked
    valid = jnp.asarray(valid)
    out = flash_attention_bhtd(q, k, v, valid, causal=True, boundary=64,
                               block_q=16, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, boundary=64,
                                   k_valid=valid)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_one_head_all_invalid_rows_zero():
    """All keys invalid on ONE KV head: that head's outputs are exactly
    zero, the other heads are untouched."""
    b, h, hkv, tq, tk, d = 1, 4, 2, 16, 64, 16
    q = jax.random.normal(jax.random.fold_in(KEY, 14), (b, h, tq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 15), (b, hkv, tk, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 16), (b, hkv, tk, d))
    valid = np.ones((b, hkv, tk), bool)
    valid[:, 0, :] = False
    valid = jnp.asarray(valid)
    out = flash_attention_bhtd(q, k, v, valid, causal=False,
                               block_q=16, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=False, k_valid=valid)
    assert float(jnp.abs(out[:, 0::hkv][:, :1]).max()) >= 0  # shape sanity
    assert float(jnp.abs(out[:, : h // hkv]).max()) == 0.0   # head group 0
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_causal_first_row_masked_key_yields_zeros():
    """Causal, boundary=0, key 0 invalid: query row 0 attends NOTHING —
    the finalize divide must produce zeros, not NaN."""
    b, h, tq, tk, d = 1, 2, 8, 8, 16
    q = jax.random.normal(jax.random.fold_in(KEY, 17), (b, h, tq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 18), (b, h, tk, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 19), (b, h, tk, d))
    valid = jnp.asarray(np.array([[False] + [True] * (tk - 1)]))
    out = flash_attention_bhtd(q, k, v, valid, causal=True,
                               block_q=8, block_k=8)
    assert bool(jnp.isfinite(out).all())
    assert float(jnp.abs(out[:, :, 0]).max()) == 0.0
    want = ref.flash_attention_ref(q, k, v, causal=True, k_valid=valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# tentpole: gather-free fused selected attention ≡ staged materialize+attend
# ---------------------------------------------------------------------------

from repro.configs.base import QuokaConfig              # noqa: E402
from repro.core import plan as plan_mod                 # noqa: E402
from repro.kernels import ops as kops                   # noqa: E402


def _staged_selected(q, k, v, key_pos, idx, start, g):
    """The staged pipeline the fused kernel replaces: plan.materialize's
    gather + [selected | causal-chunk] ops.attention over the concat."""
    b, chunk = q.shape[0], q.shape[1]
    n_kv = k.shape[2]
    idx = jnp.asarray(idx, jnp.int32)
    if g == 1 and idx.ndim == 2:        # head-shared token plan
        idx = jnp.broadcast_to(idx[:, None, :], (b, n_kv, idx.shape[-1]))
    sel = plan_mod.materialize(plan_mod.SelectionPlan(idx=idx), k, v,
                               key_pos, jnp.int32(start),
                               QuokaConfig(granularity=g))
    s = int(start)
    kc, vc = k[:, s:s + chunk], v[:, s:s + chunk]
    pc = key_pos[:, s:s + chunk]
    k_valid = jnp.concatenate(
        [sel.pos >= 0,
         jnp.broadcast_to((pc >= 0)[:, None, :], (b, n_kv, chunk))], axis=-1)
    return kops.attention(q, jnp.concatenate([sel.k, kc], axis=1),
                          jnp.concatenate([sel.v, vc], axis=1), k_valid,
                          causal=True, boundary=sel.pos.shape[-1],
                          backend="xla")


def _fused_case(case, rng_base=21):
    """(q, k, v, key_pos, idx, start, g) for one geometry tuple."""
    g, b, h, n_kv, T, chunk, start, nsel, seed = case
    q = jax.random.normal(jax.random.fold_in(KEY, rng_base + seed),
                          (b, chunk, h, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, rng_base + seed + 1),
                          (b, T, n_kv, 16))
    v = jax.random.normal(jax.random.fold_in(KEY, rng_base + seed + 2),
                          (b, T, n_kv, 16))
    key_pos = jnp.arange(T, dtype=jnp.int32)[None].repeat(b, 0)
    rng = np.random.default_rng(seed)
    if g > 1:
        hi = -(-max(start, 1) // g)     # blocks touching prior context,
        idx = np.full((b, nsel), -1, np.int32)      # straddlers included
        for bi in range(b):
            n = min(nsel - 1, hi)
            idx[bi, :n] = rng.choice(hi, size=n, replace=False)
    else:
        idx = np.full((b, n_kv, nsel), -1, np.int32)
        for bi in range(b):
            for hh in range(n_kv):
                n = min(nsel - 1, max(start, 1))
                idx[bi, hh, :n] = rng.choice(
                    max(start + 2, 1), size=n, replace=False)  # some >= start
    return q, k, v, key_pos, jnp.asarray(idx), start, g


FUSED_CASES = [
    # (g, b, h, n_kv, T, chunk, start, n_sel_slots, seed)
    (16, 1, 4, 2, 256, 32, 48, 4, 0),      # block plan, aligned start
    (16, 2, 4, 2, 256, 32, 52, 4, 1),      # ragged start straddles a block
    (16, 1, 4, 4, 128, 16, 0, 3, 2),       # first chunk: nothing selectable
    (16, 1, 2, 1, 256, 1, 37, 5, 3),       # decode: t=1, misaligned start
    (1, 1, 4, 2, 128, 16, 80, 24, 4),      # token plan, per-KV-head idx
    (1, 1, 2, 2, 96, 32, 33, 17, 5),       # ragged chunk/boundary, g=1
]


@pytest.mark.parametrize("case", FUSED_CASES)
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_selected_attention_matches_staged(case, backend):
    q, k, v, key_pos, idx, start, g = _fused_case(case)
    want = _staged_selected(q, k, v, key_pos, idx, start, g)
    out = kops.selected_attention(q, k, v, key_pos, idx, jnp.int32(start),
                                  granularity=g, backend=backend)
    assert out.shape == q.shape
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_selected_attention_shared_token_plan_2d_idx():
    """g == 1 with a head-shared (b, B) plan broadcasts across KV heads."""
    q, k, v, key_pos, idx3, start, g = _fused_case((1, 1, 4, 2, 128, 16,
                                                    64, 12, 6))
    idx2 = idx3[:, 0]
    want = _staged_selected(q, k, v, key_pos, idx2, start, 1)
    for backend in ("xla", "pallas_interpret"):
        out = kops.selected_attention(q, k, v, key_pos, idx2,
                                      jnp.int32(start), granularity=1,
                                      backend=backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_selected_attention_invalid_cache_slots():
    """key_pos == -1 (never-written cache rows) are masked inside the
    kernel even when the plan selects their block."""
    g, b, h, n_kv, T, chunk = 16, 1, 4, 2, 128, 16
    start = 48
    q = jax.random.normal(jax.random.fold_in(KEY, 31), (b, chunk, h, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, 32), (b, T, n_kv, 16))
    v = jax.random.normal(jax.random.fold_in(KEY, 33), (b, T, n_kv, 16))
    key_pos = np.arange(T, dtype=np.int32)[None].repeat(b, 0)
    key_pos[:, 16:32] = -1              # block 1 was never written
    key_pos = jnp.asarray(key_pos)
    idx = jnp.asarray([[0, 1, 2, -1]], jnp.int32)
    want = _staged_selected(q, k, v, key_pos, idx, start, g)
    for backend in ("xla", "pallas_interpret"):
        out = kops.selected_attention(q, k, v, key_pos, idx,
                                      jnp.int32(start), granularity=g,
                                      backend=backend)
        assert bool(jnp.isfinite(out).all())
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_selected_attention_paged_block_table():
    """The paged path attends THROUGH the block table: permuted physical
    blocks, stale junk in unmapped blocks and a -1 table hole must all
    match the staged path on the equivalent linear view."""
    g, b, h, n_kv, d, bs = 16, 2, 4, 2, 16, 16
    nb_logical, chunk, start = 8, 16, 96
    T = nb_logical * bs
    N = nb_logical * b + 3              # spare physical blocks
    rng = np.random.default_rng(7)
    q = jax.random.normal(jax.random.fold_in(KEY, 41), (b, chunk, h, d))
    k_lin = jax.random.normal(jax.random.fold_in(KEY, 42), (b, T, n_kv, d))
    v_lin = jax.random.normal(jax.random.fold_in(KEY, 43), (b, T, n_kv, d))
    pos_lin = np.arange(T, dtype=np.int32)[None].repeat(b, 0)
    pos_lin[:, start + chunk:] = -1     # beyond the written prefix
    # scatter the linear views into a permuted pool; poison the spares
    perm = rng.permutation(N)
    k_pool = np.array(
        jax.random.normal(jax.random.fold_in(KEY, 44), (N, bs, n_kv, d)))
    v_pool = np.array(
        jax.random.normal(jax.random.fold_in(KEY, 45), (N, bs, n_kv, d)))
    pos_pool = rng.integers(0, T, (N, bs)).astype(np.int32)  # stale pos >= 0
    table = np.full((b, nb_logical), -1, np.int32)
    for bi in range(b):
        for lb in range(nb_logical):
            phys = int(perm[bi * nb_logical + lb])
            table[bi, lb] = phys
            k_pool[phys] = np.asarray(k_lin[bi, lb * bs:(lb + 1) * bs])
            v_pool[phys] = np.asarray(v_lin[bi, lb * bs:(lb + 1) * bs])
            pos_pool[phys] = pos_lin[bi, lb * bs:(lb + 1) * bs]
    table[1, -1] = -1                   # one unmapped logical block
    pos_lin[1, (nb_logical - 1) * bs:] = -1
    pos_lin = jnp.asarray(pos_lin)
    idx = jnp.asarray([[0, 2, 4, -1], [1, 3, 7, -1]], jnp.int32)
    want = _staged_selected(q, k_lin, v_lin, pos_lin, idx, start, g)
    for backend in ("xla", "pallas_interpret"):
        out = kops.selected_attention(
            q, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(pos_pool), idx, jnp.int32(start),
            granularity=g, backend=backend, table=jnp.asarray(table),
            block_size=bs)
        assert bool(jnp.isfinite(out).all())
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.compiled
def test_compiled_kernels_match_oracle():
    """Compiled (non-interpret) Pallas kernels vs the XLA oracles.  Skips
    VISIBLY on hosts without a Pallas-compilable accelerator — the
    hardware-gated CI job runs it on real TPUs."""
    if jax.default_backend() == "cpu":
        pytest.skip("compiled Pallas kernels need a TPU/GPU backend; "
                    "CPU CI covers the interpret-mode parity suite")
    # the fused selected-attention kernel does not compile for Mosaic
    # (tests/test_tpu_compile.py pins that); flash and scoring do
    q, k, v, key_pos, *_ = _fused_case(FUSED_CASES[0])
    o_flash = flash_attention(q, k, v, backend="pallas")
    w_flash = flash_attention(q, k, v, backend="xla")
    np.testing.assert_allclose(np.asarray(o_flash), np.asarray(w_flash),
                               atol=2e-2, rtol=2e-2)
    valid = key_pos >= 0
    qbar = k[:, :8] / jnp.linalg.norm(k[:, :8], axis=-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(kops.score(qbar, k, valid, backend="pallas")),
        np.asarray(kops.score(qbar, k, valid, backend="xla")),
        atol=2e-2, rtol=2e-2)
