"""Tensor-parallel sharded serving: token parity + collective hygiene.

Everything runs in ONE subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the flag must be
set before jax initialises, and must never leak into this process — smoke
tests and benchmarks need one real device) on a ``(data=2, model=4)`` host
mesh, the regime the old ``core/quoka.py`` §Perf A7 note documented:
granite's smoke config has n_kv = 2 < |model| = 4, so the score tensor
under-shards and the T-local shard_map path must engage.

Checked:
  * ``generate`` and greedy ``serve`` on the mesh are token-identical to
    the unsharded engine for ``full`` AND ``quoka``, including a second
    serve pass admitted through prefix-cache hits over a warm pool.
  * the sharded scoring pass issues no full-cache all-gather: the compiled
    HLO of a jitted ``plan.select`` carries only the candidate-merge
    all-gather (a few hundred bytes), orders of magnitude below the K
    cache it used to reshard (analysis/hlo.py byte accounting).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import sys
    sys.path.insert(0, __SRC__)
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.analysis import hlo
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build_model
    from repro.serving.engine import Engine
    from repro.serving.request import make_requests
    from repro.sharding import ctx as shctx

    cfg = get_config("granite-3-2b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_host_mesh(model=4, data=2)
    assert cfg.n_kv_heads % 4 != 0      # the documented under-sharding case
    rng = np.random.default_rng(3)
    prompts = [rng.integers(3, cfg.vocab, (n,)).astype(np.int32)
               for n in (16, 48, 29)]
    out = {}
    for method in ("full", "quoka"):
        ref = Engine(model, params, method=method)
        shd = Engine(model, params, method=method, mesh=mesh)
        toks = np.stack([prompts[1], prompts[1][::-1].copy()])
        rg = ref.generate(ref.pad_prompt(toks), 6)
        sg = shd.generate(shd.pad_prompt(toks), 6)
        out[method + "/generate"] = bool(np.array_equal(rg.tokens, sg.tokens))

        kw = dict(block_size=16, max_decode_batch=4, max_prefill_tokens=32)
        r1 = ref.serve(make_requests(prompts, 5), **kw)
        st = shd.make_serve_state(make_requests(prompts, 5), **kw)
        s1 = shd.serve(make_requests(prompts, 5), state=st)
        s2 = shd.serve(make_requests(prompts, 5), state=st)   # warm pool
        out[method + "/serve"] = all(
            np.array_equal(r1.tokens[i], s1.tokens[i])
            for i in range(len(prompts)))
        out[method + "/serve_prefix_hit"] = all(
            np.array_equal(s1.tokens[i], s2.tokens[i])
            for i in range(len(prompts)))
        out[method + "/cache_hits"] = int(shd.stats["cache_hits"])

    # ---- HLO: the sharded scoring pass must not reshard the K cache ----
    from repro.core import plan as plan_mod
    b, t, h, n_kv, d = 2, 64, cfg.n_heads, cfg.n_kv_heads, \\
        cfg.resolved_head_dim
    q = jax.random.normal(jax.random.PRNGKey(1), (b, 16, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (b, t, n_kv, d),
                          jnp.float32)
    pos = jnp.arange(t, dtype=jnp.int32)[None].repeat(b, 0)
    fn = jax.jit(lambda q, k, v, p: plan_mod.select(
        "quoka", q, k, v, p, jnp.asarray(48), cfg.quoka))
    snap = shctx.get_policy()
    shctx.set_policy(mesh, ("data",))
    try:
        with mesh:
            comp = fn.lower(q, k, k, pos).compile()
    finally:
        shctx.restore_policy(snap)
    coll = hlo.collective_bytes(comp.as_text())
    k_bytes = b * t * n_kv * d * 4
    out["score_allgather_bytes"] = coll.get("all-gather", 0)
    out["k_cache_bytes"] = k_bytes

    # ---- Pallas kernels under the mesh run per device (shard_map) ----
    from repro.kernels import ops as kops
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q8 = jax.random.normal(ks[0], (2, 16, 8, 64), jnp.float32)
    k4 = jax.random.normal(ks[1], (2, 48, 4, 64), jnp.float32)
    v4 = jax.random.normal(ks[2], (2, 48, 4, 64), jnp.float32)
    kv_ok = jax.random.bernoulli(ks[3], 0.8, (2, 4, 48))
    qbar = jax.random.normal(ks[4], (2, 4, 4, 64), jnp.float32)

    def kernels(q, k, v, ok, qb):
        return (kops.attention(q, k, v, ok, boundary=32,
                               backend="pallas_interpret"),
                kops.score(qb, k, ok[:, 0], backend="pallas_interpret"))

    args = (q8, k4, v4, kv_ok, qbar)
    want = jax.jit(kernels)(*args)
    shctx.set_policy(mesh, ("data",))
    try:
        with mesh:
            got = jax.jit(kernels)(*args)
            jaxpr = str(jax.make_jaxpr(kernels)(*args))
    finally:
        shctx.restore_policy(snap)
    out["pallas_per_device"] = bool(
        jaxpr.count("shard_map") >= 2 and all(
            np.allclose(np.asarray(g), np.asarray(w), atol=1e-5)
            for g, w in zip(got, want)))
    print("RESULT", json.dumps(out))
""")


@pytest.fixture(scope="module")
def subproc_result():
    code = SUBPROC.replace("__SRC__", repr(os.path.abspath(SRC)))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    for line in res.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"subprocess failed:\n{res.stderr[-3000:]}")


@pytest.mark.slow
@pytest.mark.parametrize("method", ["full", "quoka"])
def test_sharded_token_parity(subproc_result, method):
    """Sharded generate/serve == unsharded, token for token, incl. a
    prefix-cache-hit admission over a warm pool."""
    assert subproc_result[f"{method}/generate"], subproc_result
    assert subproc_result[f"{method}/serve"], subproc_result
    assert subproc_result[f"{method}/serve_prefix_hit"], subproc_result
    assert subproc_result[f"{method}/cache_hits"] > 0, subproc_result


@pytest.mark.slow
def test_sharded_scoring_no_kv_cache_allgather(subproc_result):
    """Resolution of the old core/quoka.py §Perf A7 note: under tensor
    parallelism with an indivisible KV-head axis, the scoring pass moves
    only per-shard top-k candidates — never the K cache."""
    ag = subproc_result["score_allgather_bytes"]
    kb = subproc_result["k_cache_bytes"]
    assert ag > 0, "shard_map path did not engage (no candidate merge)"
    assert ag < kb / 4, (ag, kb)


def test_sharded_pallas_kernels_run_per_device(subproc_result):
    """GSPMD cannot partition a Mosaic kernel: under the mesh policy the
    flash and scoring kernels run inside a shard_map (batch over data,
    heads over model) and equal the meshless call."""
    assert subproc_result["pallas_per_device"], subproc_result
