"""SelectionPlan (core/plan.py): the staged score -> select -> materialize
pipeline, block granularity, cross-layer reuse and the contiguous-gather
invariant the paged serving path relies on.

The sharded half runs in one subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same pattern as
test_sharded_serving.py): plan indices built through the T-local shard_map
candidate path must be bit-identical to the meshless build.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo
from repro.configs import get_config
from repro.configs.base import QuokaConfig
from repro.core import plan as plan_mod
from repro.core import selection as sel_scores
from repro.core.attention import NEG_INF
from repro.core.chunked_prefill import output_error
from repro.core.quoka import Selected, prior_context_valid
from repro.data.synthetic import structured_qkv
from repro.models.model import build_model
from repro.serving import pool as pl

KEY = jax.random.PRNGKey(0)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# staged pipeline contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("granularity", [1, 16])
def test_staged_equals_fused(granularity):
    """build + materialize is exactly select, and the plan's static shape
    is plan_idx_shape's."""
    b, t, h, n_kv, d = 2, 64, 4, 2, 16
    q = jax.random.normal(KEY, (b, 16, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, n_kv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, n_kv, d))
    pos = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    cfg = QuokaConfig(budget=32, n_queries=8, keep_first=4,
                      granularity=granularity)
    start = jnp.asarray(48)
    pln = plan_mod.build("quoka", q, k, pos, start, cfg)
    assert pln.idx.shape == plan_mod.plan_idx_shape(cfg, b, n_kv, t)
    sel = plan_mod.materialize(pln, k, v, pos, start, cfg)
    ref = plan_mod.select("quoka", q, k, v, pos, start, cfg)
    for a, r in zip(sel, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_block_plan_is_shared_across_heads():
    """g > 1 plans carry BLOCK ids shared by every KV head (a per-head
    block plan could not be a block-table sub-view), and materialize
    broadcasts identical per-token metadata to each head."""
    b, t, n_kv, d = 1, 64, 2, 8
    k = jax.random.normal(KEY, (b, t, n_kv, d))
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    cfg = QuokaConfig(granularity=8, keep_first=0)
    scores = jax.random.normal(jax.random.fold_in(KEY, 3), (b, n_kv, t))
    pln = plan_mod.plan_from_scores(scores.astype(jnp.float32), pos, cfg,
                                    budget=32)
    assert pln.idx.shape == (b, 4)                       # blocks, not tokens
    sel = plan_mod.materialize(pln, k, k, pos, jnp.asarray(t), cfg)
    np.testing.assert_array_equal(np.asarray(sel.pos[0, 0]),
                                  np.asarray(sel.pos[0, 1]))


def test_block_full_budget_matches_dense():
    """Equivalence gate at block granularity: budget >= T selects every
    prior block, so chunked output == dense causal attention."""
    q = jax.random.normal(KEY, (1, 128, 4, 16))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 128, 2, 16))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 128, 2, 16))
    cfg = QuokaConfig(chunk_size=32, budget=128, granularity=16,
                      n_queries=8, keep_first=0)
    assert float(output_error(q, k, v, cfg, "quoka")) < 2e-3


def test_block_union_across_chunk_boundary():
    """A block straddling the chunk boundary is selected WHOLE; its
    not-yet-prior tokens come back as pos = -1 budget padding with the
    slot index re-derived at materialize time."""
    t, g = 32, 8
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    start = 12                                 # boundary inside block 1
    tok = jnp.arange(t)
    scores = jnp.where(tok < start, jnp.where(tok >= 8, 5.0, 1.0),
                       NEG_INF)[None, None, :].astype(jnp.float32)
    cfg = QuokaConfig(granularity=g, keep_first=0)
    pln = plan_mod.plan_from_scores(scores, pos, cfg, budget=16)
    # block 1 (max 5.0) then block 0 (max 1.0); blocks 2/3 are all-invalid
    np.testing.assert_array_equal(np.asarray(pln.idx), [[1, 0]])
    k = jax.random.normal(KEY, (1, t, 1, 4))
    sel = plan_mod.materialize(pln, k, k, pos, jnp.asarray(start), cfg)
    np.testing.assert_array_equal(
        np.asarray(sel.pos[0, 0]),
        [8, 9, 10, 11, -1, -1, -1, -1, 0, 1, 2, 3, 4, 5, 6, 7])
    got = np.asarray(sel.idx[0, 0])
    want = np.asarray([8, 9, 10, 11, -1, -1, -1, -1] + list(range(8)))
    np.testing.assert_array_equal(got, want)
    valid = want >= 0
    np.testing.assert_allclose(np.asarray(sel.k[0, valid, 0]),
                               np.asarray(k[0, want[valid], 0]))


def test_block_granularity_accuracy_delta_bounded():
    """Accuracy proxy (paper eq. (4)): selecting whole 16-token blocks
    instead of tokens costs a bounded output-error delta at half budget."""
    q, k, v = structured_qkv(jax.random.PRNGKey(3), 2, 512, 8, 2, 32)
    tok = QuokaConfig(chunk_size=128, budget=256, n_queries=16, keep_first=4)
    blk = dataclasses.replace(tok, granularity=16)
    err_tok = float(output_error(q, k, v, tok, "quoka"))
    err_blk = float(output_error(q, k, v, blk, "quoka"))
    assert err_blk < 0.5, (err_tok, err_blk)
    assert err_blk <= err_tok + 0.15, (err_tok, err_blk)


# ---------------------------------------------------------------------------
# materialize's validity route == the four-gather oracle, bit for bit
# ---------------------------------------------------------------------------

def _oracle_materialize(plan, k, v, key_pos, chunk_start, cfg):
    """The earlier materialize, kept verbatim as the oracle: it gathers the
    positions AND the cache view's validity mask at every selected slot."""
    b, t, n_kv, d = k.shape
    g = plan_mod.grid(cfg)
    valid = prior_context_valid(key_pos, chunk_start)            # (b, T)
    if g == 1:
        top_i = plan.idx                                         # (b,n_kv,B)
        safe = jnp.maximum(top_i, 0)
        idx_t = safe.transpose(0, 2, 1)[..., None]               # (b,B,n_kv,1)
        k_sel = jnp.take_along_axis(k, idx_t, axis=1)            # (b,B,n_kv,d)
        v_sel = jnp.take_along_axis(v, idx_t, axis=1)
        shape = top_i.shape[:2] + (t,)
        pos = jnp.take_along_axis(
            jnp.broadcast_to(key_pos[:, None, :], shape), safe, axis=2)
        ok = jnp.take_along_axis(
            jnp.broadcast_to(valid[:, None, :], shape), safe, axis=2)
        good = (top_i >= 0) & ok
        return Selected(k=k_sel, v=v_sel, pos=jnp.where(good, pos, -1),
                        idx=jnp.where(good, top_i, -1))
    nb = plan.idx.shape[1]
    blocks = jnp.maximum(plan.idx, 0)                            # (b, NB)
    kb = k.reshape(b, t // g, g, n_kv, d)
    ib = blocks[:, :, None, None, None]
    k_sel = jnp.take_along_axis(kb, ib, axis=1).reshape(b, nb * g, n_kv, d)
    v_sel = jnp.take_along_axis(v.reshape(b, t // g, g, n_kv, d), ib,
                                axis=1).reshape(b, nb * g, n_kv, d)
    pos_sel = jnp.take_along_axis(key_pos.reshape(b, t // g, g),
                                  blocks[:, :, None], axis=1)    # (b, NB, g)
    ok_sel = jnp.take_along_axis(valid.reshape(b, t // g, g),
                                 blocks[:, :, None], axis=1)
    good = ok_sel & (plan.idx >= 0)[:, :, None]
    pos_flat = jnp.where(good, pos_sel, -1).reshape(b, nb * g)
    slot = blocks[:, :, None] * g + jnp.arange(g, dtype=jnp.int32)
    idx_flat = jnp.where(good, slot, -1).reshape(b, nb * g)
    return Selected(
        k=k_sel, v=v_sel,
        pos=jnp.broadcast_to(pos_flat[:, None, :], (b, n_kv, nb * g)),
        idx=jnp.broadcast_to(idx_flat[:, None, :], (b, n_kv, nb * g)))


def _views(case):
    """(key_pos (2, 64), chunk_start, q_valid, keep_first) per input kind.
    Row 0 and row 1 differ in every kind so both layouts are exercised."""
    t = 64
    ar = np.arange(t, dtype=np.int32)
    pos = np.stack([ar, ar])
    start = np.asarray(48, np.int32)
    q_valid = None
    keep_first = 0
    if case == "pads":              # left-pad slots and an empty cache tail
        pos[0, :8] = -1
        pos[0, 8:] = ar[:t - 8]
        pos[1, 56:] = -1
    elif case == "ragged":          # rows at different chunk starts
        start = np.asarray([40, 56], np.int32)
        q_valid = np.ones((2, 8), bool)
        q_valid[1, 5:] = False
    elif case == "holes":           # -1 stamps in the middle (prefix COW)
        pos[0, 10:14] = -1
        pos[0, 30] = -1
        pos[1, 20:28] = -1
    elif case == "short":           # fewer prior slots than the budget
        start = np.asarray([20, 12], np.int32)
    elif case == "sinks":           # sinks behind pads and next to a hole
        keep_first = 4
        pos[0, 1] = -1
        pos[1, :5] = -1
        pos[1, 5:] = ar[:t - 5]
    else:
        raise ValueError(case)
    qv = None if q_valid is None else jnp.asarray(q_valid)
    return jnp.asarray(pos), jnp.asarray(start), qv, keep_first


def _assert_same(sel, ref):
    for name in Selected._fields:
        np.testing.assert_array_equal(np.asarray(getattr(sel, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(sel.valid),
                                  np.asarray(ref.pos >= 0))


@pytest.mark.parametrize("case", ["pads", "ragged", "holes", "short",
                                  "sinks"])
@pytest.mark.parametrize("method", [m for m in sel_scores.METHODS
                                    if m != "full"])
@pytest.mark.parametrize("reuse", ["fresh", "carry"])
@pytest.mark.parametrize("granularity", [1, 16])
def test_materialize_matches_gathered_mask_oracle(granularity, reuse,
                                                  method, case):
    """Selected.k/.v/.pos/.idx equal the mask-gathering oracle bit for bit,
    and ``valid`` is the oracle's ``pos >= 0``.  A fresh plan reads its
    validity off its indices; a carried plan consumed under a later layer's
    view (new holes, an earlier chunk start) must re-derive it."""
    b, t, h, n_kv, d = 2, 64, 4, 2, 16
    key_pos, start, q_valid, keep_first = _views(case)
    q = jax.random.normal(KEY, (b, 8, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, n_kv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, n_kv, d))
    cfg = QuokaConfig(budget=32, n_queries=4, keep_first=keep_first,
                      granularity=granularity, rank=8,
                      reuse_interval=2 if reuse == "carry" else 1)
    if reuse == "fresh":
        pln = plan_mod.build(method, q, k, key_pos, start, cfg,
                             q_valid=q_valid)
        ref = _oracle_materialize(pln, k, v, key_pos, start, cfg)
        _assert_same(plan_mod.select(method, q, k, v, key_pos, start, cfg,
                                     q_valid=q_valid), ref)
        sel, carry = plan_mod.select_with_ctx(
            {"layer_idx": 0}, None, method, q, k, v, key_pos, start, cfg,
            q_valid=q_valid)
        assert carry is None
        _assert_same(sel, ref)
        return
    carry = plan_mod.empty_carry(plan_mod.plan_idx_shape(cfg, b, n_kv, t))
    sel0, carry = plan_mod.select_with_ctx(
        {"layer_idx": 0}, carry, method, q, k, v, key_pos, start, cfg,
        q_valid=q_valid)
    _assert_same(sel0, _oracle_materialize(
        plan_mod.SelectionPlan(idx=carry.idx), k, v, key_pos, start, cfg))
    # layer 1 reuses layer 0's plan under a view with new holes and an
    # earlier chunk start, so some carried slots are no longer selectable
    pos1 = key_pos.at[:, 2:6].set(-1)
    start1 = start - 8
    sel1, carry1 = plan_mod.select_with_ctx(
        {"layer_idx": 1}, carry, method, q, k, v, pos1, start1, cfg,
        q_valid=q_valid)
    np.testing.assert_array_equal(np.asarray(carry1.idx),
                                  np.asarray(carry.idx))
    ref = _oracle_materialize(plan_mod.SelectionPlan(idx=carry.idx), k, v,
                              pos1, start1, cfg)
    _assert_same(sel1, ref)
    planned = int((carry.idx >= 0).sum()) * (
        1 if granularity == 1 else granularity * n_kv)
    assert int(ref.valid.sum()) < planned, "reuse case masks nothing"


# ---------------------------------------------------------------------------
# cross-layer reuse
# ---------------------------------------------------------------------------

def test_refresh_cadence_and_corrections():
    """refresh rebuilds at layer % interval == 0 and at correction layers,
    reuses the carried indices in between."""
    shape = (1, 4)
    cfg = QuokaConfig(reuse_interval=2, correction_layers=(3,))
    mk = lambda tag: (lambda: plan_mod.SelectionPlan(
        idx=jnp.full(shape, tag, jnp.int32)))
    carry = plan_mod.empty_carry(shape)
    seen = []
    for li in range(6):
        pln, carry = plan_mod.refresh(carry, li, cfg, mk(li))
        assert carry is not None and bool(carry.valid)
        seen.append(int(pln.idx[0, 0]))
    assert seen == [0, 0, 2, 3, 4, 4]
    # no carry (reuse disabled / unsupported geometry): build every layer
    pln, carry = plan_mod.refresh(None, 5, cfg, mk(7))
    assert carry is None and int(pln.idx[0, 0]) == 7


GRANITE = get_config("granite-3-2b").smoke(n_layers=4)


def _quoka_variant(**kw):
    return dataclasses.replace(
        GRANITE, quoka=dataclasses.replace(GRANITE.quoka, **kw))


@pytest.fixture(scope="module")
def granite_params():
    # params do not depend on QuokaConfig: one init serves every variant
    return build_model(GRANITE).init(jax.random.PRNGKey(0))


def _prefill_logits(cfg, params, toks):
    model = build_model(cfg)
    cache = model.init_cache(toks.shape[0], toks.shape[1])
    logits, _ = model.prefill(params, {"tokens": toks}, cache, "quoka")
    return np.asarray(logits)


@pytest.mark.slow
def test_corrections_everywhere_equal_interval_one(granite_params):
    """reuse_interval=4 with correction layers covering EVERY layer must
    rebuild everywhere — token-identical to reuse_interval=1."""
    toks = jax.random.randint(KEY, (2, 96), 3, GRANITE.vocab)
    base = _prefill_logits(_quoka_variant(reuse_interval=1), granite_params,
                           toks)
    corr = _prefill_logits(
        _quoka_variant(reuse_interval=4, correction_layers=(0, 1, 2, 3)),
        granite_params, toks)
    np.testing.assert_allclose(corr, base, atol=1e-6, rtol=1e-6)


@pytest.mark.slow
def test_reuse_interval_engages_and_decodes(granite_params):
    """Plans reused across layers actually change the computation (layers
    1..3 consume layer 0's plan), and the decode path carries plans too."""
    from repro.serving.engine import Engine
    toks = jax.random.randint(KEY, (2, 96), 3, GRANITE.vocab)
    base = _prefill_logits(_quoka_variant(reuse_interval=1), granite_params,
                           toks)
    reused = _prefill_logits(_quoka_variant(reuse_interval=4),
                             granite_params, toks)
    assert not np.allclose(reused, base, atol=1e-6), \
        "reuse_interval=4 produced bit-identical logits: carry not engaged"
    cfg = _quoka_variant(reuse_interval=2)
    eng = Engine(build_model(cfg), granite_params, method="quoka")
    out = eng.generate(eng.pad_prompt(np.asarray(toks)), 4)
    tok = np.asarray(out.tokens)
    assert tok.shape == (2, 4)                           # the new tokens
    assert (tok >= 0).all() and (tok < cfg.vocab).all()


# ---------------------------------------------------------------------------
# paged pool: plans as block-table sub-views + the contiguity invariant
# ---------------------------------------------------------------------------

def _pool_data(num_blocks, block_size, n_kv, d):
    k = jax.random.normal(KEY, (1, num_blocks, block_size, n_kv, d))
    pos = jnp.arange(num_blocks * block_size, dtype=jnp.int32).reshape(
        1, num_blocks, block_size)
    return {"k": k, "pos": pos}


def test_gather_blocks_is_block_table_subview():
    bs, n_kv, d = 4, 2, 4
    data = _pool_data(6, bs, n_kv, d)
    table = jnp.asarray([[0, 1, 2], [3, 4, -1]], jnp.int32)
    ids = jnp.asarray([[2, 0], [1, -1]], jnp.int32)      # logical, -1 pad
    out = pl.gather_blocks(data, table, ids, 6, bs)
    assert out["k"].shape == (1, 2, 2 * bs, n_kv, d)
    np.testing.assert_allclose(np.asarray(out["k"][0, 0, :bs]),
                               np.asarray(data["k"][0, 2]))
    np.testing.assert_allclose(np.asarray(out["k"][0, 0, bs:]),
                               np.asarray(data["k"][0, 0]))
    np.testing.assert_allclose(np.asarray(out["k"][0, 1, :bs]),
                               np.asarray(data["k"][0, 4]))
    # padding ids read as pos = -1 (and zero payload), like empty table slots
    assert (np.asarray(out["pos"][0, 1, bs:]) == -1).all()
    assert (np.asarray(out["k"][0, 1, bs:]) == 0).all()


def test_materialize_hlo_contiguous_block_slices():
    """The invariant the paged path relies on: at g > 1 every KV-payload
    gather in the compiled module moves whole g-token slabs — slice_sizes
    span the block extent, no per-token gather."""
    b, t, n_kv, d, g = 2, 128, 4, 64, 16
    cfg = QuokaConfig(granularity=g, keep_first=0)
    k = jax.random.normal(KEY, (b, t, n_kv, d))
    pos = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    idx = jnp.asarray([[0, 2, 5, -1]] * b, jnp.int32)
    fn = jax.jit(lambda i, k, v: plan_mod.materialize(
        plan_mod.SelectionPlan(idx=i), k, v, pos, jnp.asarray(t), cfg))
    txt = fn.lower(idx, k, k).compile().as_text()
    sizes = hlo.gather_slice_sizes(txt)
    payload = [s for s in sizes if d in s]
    assert payload, f"no KV-payload gather found: {sizes}"
    assert all(g in s for s in payload), \
        f"per-token gather on the KV payload: {sizes}"


def _serving_step_texts(registry):
    """Compiled HLO text of the engine's jitted (prefill, decode) steps for
    the qwen3 smoke config with quoka at granularity 1.  Prompts exceed the
    32-token budget so both steps select."""
    from repro.serving.engine import Engine
    from repro.serving.request import make_requests
    cfg = get_config("qwen3-4b").smoke()
    cfg = dataclasses.replace(cfg, quoka=dataclasses.replace(
        cfg.quoka, granularity=1, budget=32, method="quoka"))
    model = build_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), method="quoka",
                 registry=registry)
    reqs = make_requests([list(range(1, 90)), list(range(7, 60))], 3)
    st = eng.make_serve_state(reqs, block_size=16, max_decode_batch=2)
    args = {}

    def capture(i):
        def fn(*a):
            args[i] = a
            return st.fns[i](*a)
        return fn

    eng.serve(reqs, state=dataclasses.replace(
        st, fns=(capture(0), capture(1))))
    assert set(args) == {0, 1}, "a step program never ran"
    d = cfg.resolved_head_dim
    return d, [st.fns[i].lower(*args[i]).compile().as_text() for i in (0, 1)]


@pytest.mark.parametrize("layer_obs", [False, True])
def test_materialize_hlo_gathers_follow_reader(layer_obs):
    """A fresh token plan materializes with two gathers, the K and V rows:
    the cache view's validity is never gathered, and the positions gather
    is dead code when nothing reads positions as values.  With LayerObs on
    (a registry at construction) telemetry reads ``Selected.pos``, so a
    positions gather is back — the route follows the reader."""
    from repro.obs.registry import Registry
    d, texts = _serving_step_texts(Registry() if layer_obs else None)
    for text in texts:
        sizes = hlo.gathers_in_scope(text, "plan_materialize")
        assert sizes, "plan_materialize scope not visible in the HLO"
        rows = [s for s in sizes if s[-1] == d]
        scalar = [s for s in sizes if all(x == 1 for x in s)]
        if layer_obs:
            assert scalar, f"no positions gather with LayerObs on: {sizes}"
        else:
            assert len(sizes) == 2 and rows == sizes, sizes
            assert not scalar, sizes


def test_gather_blocks_hlo_contiguous_block_slices():
    """Same invariant on the pool side: gather_blocks lowers to one
    dynamic block_size-row slice per selected block for every pool leaf."""
    bs, n_kv, d = 16, 2, 8
    data = _pool_data(8, bs, n_kv, d)
    table = jnp.zeros((2, 4), jnp.int32)
    ids = jnp.zeros((2, 2), jnp.int32)
    fn = jax.jit(lambda dat, tb, bi: pl.gather_blocks(dat, tb, bi, 8, bs))
    txt = fn.lower(data, table, ids).compile().as_text()
    sizes = hlo.gather_slice_sizes(txt)
    payload = [s for s in sizes if len(s) >= 3]          # pool data leaves
    assert payload, f"no pool-leaf gather found: {sizes}"
    assert all(bs in s for s in payload), \
        f"sub-block gather on a pool leaf: {sizes}"


def test_serve_rejects_grid_misaligned_block_size(granite_params):
    """make_serve_state must refuse a pool whose block grid the selection
    grid does not divide — block plans could not be table sub-views."""
    from repro.serving.engine import Engine
    from repro.serving.request import make_requests
    eng = Engine(build_model(_quoka_variant(granularity=12)),
                 granite_params, method="quoka")
    reqs = make_requests([np.arange(3, 35, dtype=np.int32)], 4)
    with pytest.raises(ValueError, match="granularity"):
        eng.make_serve_state(reqs, block_size=16)


# ---------------------------------------------------------------------------
# sharded plan candidates == meshless, bit for bit
# ---------------------------------------------------------------------------

SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import sys
    sys.path.insert(0, __SRC__)
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.analysis import hlo
    from repro.configs.base import QuokaConfig
    from repro.core import plan as plan_mod
    from repro.core import quoka as qk
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import ctx as shctx

    b, t, h, n_kv, d = 2, 128, 8, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(1), (b, 16, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (b, t, n_kv, d),
                          jnp.float32)
    pos = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    start = jnp.asarray(96)
    mesh = make_host_mesh(model=4, data=2)
    out = {}
    for g, budget in ((1, 48), (16, 64)):
        cfg = QuokaConfig(budget=budget, n_queries=8, keep_first=4,
                          granularity=g)
        ref = plan_mod.build("quoka", q, k, pos, start, cfg)
        snap = shctx.get_policy()
        shctx.set_policy(mesh, ("data",))
        try:
            with mesh:
                assert qk._tp_route(k, cfg) is not None, "TP path idle"
                got = plan_mod.build("quoka", q, k, pos, start, cfg)
                fn = jax.jit(lambda q, k, p, c=cfg: plan_mod.build(
                    "quoka", q, k, p, start, c).idx)
                txt = fn.lower(q, k, pos).compile().as_text()
        finally:
            shctx.restore_policy(snap)
        out[f"g{g}/bit_exact"] = bool(np.array_equal(
            np.asarray(ref.idx), np.asarray(got.idx)))
        out[f"g{g}/allgather_bytes"] = hlo.collective_bytes(txt).get(
            "all-gather", 0)
    out["k_cache_bytes"] = b * t * n_kv * d * 4
    print("RESULT", json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_plan_result():
    code = SUBPROC.replace("__SRC__", repr(os.path.abspath(SRC)))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    for line in res.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"subprocess failed:\n{res.stderr[-3000:]}")


@pytest.mark.slow
@pytest.mark.parametrize("g", [1, 16])
def test_sharded_plan_candidates_bit_exact(sharded_plan_result, g):
    """The T-local shard_map candidate merge returns the SAME plan indices
    as the meshless build — token slots at g=1, block ids at g=16 — and
    moves only candidates (tiny all-gather), never the K cache."""
    r = sharded_plan_result
    assert r[f"g{g}/bit_exact"], r
    ag = r[f"g{g}/allgather_bytes"]
    assert ag > 0, "shard_map candidate merge did not engage"
    assert ag < r["k_cache_bytes"] / 4, r
