"""Config system for repro models.

Every architecture in ``src/repro/configs/<id>.py`` builds a ``ModelConfig``
via plain dataclasses.  Configs are immutable; reduced ("smoke") variants are
derived with ``dataclasses.replace`` through ``ModelConfig.smoke()``.

Block-type vocabulary (see models/stack.py):
  "attn"        dense GQA attention + SwiGLU MLP
  "attn_local"  sliding-window GQA attention + SwiGLU MLP
  "mla"         DeepSeek multi-head latent attention + SwiGLU MLP
  "mla_moe"     MLA attention + MoE FFN
  "attn_moe"    GQA attention + MoE FFN
  "rwkv"        RWKV6 time-mix + channel-mix
  "mamba"       Mamba2 (SSD) block
  "mamba_shared_attn"  Mamba2 block followed by the *shared* attention block
  "enc_attn"    bidirectional attention + MLP (whisper encoder)
  "dec_cross"   causal self-attn + cross-attn + MLP (whisper decoder)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int               # per-expert FFN hidden dim
    n_shared: int = 0           # shared (always-on) experts, deepseek-style
    router_aux_coef: float = 0.01
    capacity_factor: float = 1.25
    # "dense" = weighted sum over all experts (exact, smoke-test scale);
    # "capacity" = scatter/gather dispatch with fixed capacity (production).
    dispatch: str = "dense"


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2             # d_inner = expand * d_model
    head_dim: int = 64          # mamba2 head dim


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora: int = 64        # rank of the data-dependent decay LoRA


@dataclass(frozen=True)
class EncoderConfig:
    """Whisper-style encoder over a (stubbed) conv/mel frontend."""
    n_layers: int
    n_ctx: int = 1500           # frames after conv frontend
    d_model: int = 0            # 0 -> same as decoder d_model
    n_heads: int = 0            # 0 -> same as decoder


@dataclass(frozen=True)
class FrontendConfig:
    """Stub modality frontend: input_specs() provides embeddings directly."""
    kind: str                   # "vision" | "audio"
    n_tokens: int               # patches / frames
    d_in: int                   # embedding dim produced by the stub


@dataclass(frozen=True)
class QuokaConfig:
    """Paper Algorithm 1/2 hyper-parameters."""
    chunk_size: int = 128          # B_CP
    budget: int = 1024             # B_SA
    # paper Table 2: B_SA as a fixed FRACTION of the context (25% there).
    # Under jit the budget must be static, so the ratio applies to the
    # cache capacity / prompt length rather than the running length.
    budget_ratio: Optional[float] = None
    n_queries: int = 16            # N_Q
    scoring: str = "cosine"        # "cosine" | "dot"   (Table 9 ablation)
    query_agg: str = "max"         # "max" | "mean"     (Table 10 ablation)
    # sink/local protection: always keep first `keep_first` and the current
    # chunk's own KV (the paper keeps the chunk KV by construction, eq. (2)).
    keep_first: int = 4
    method: str = "quoka"          # selection method (see core/selection.py)
    # kernel backend for the scoring + post-selection-attention hot path:
    # "auto" | "xla" | "pallas_interpret" | "pallas" — resolved by
    # kernels/ops.py::resolve_backend (env REPRO_BACKEND overrides "auto")
    backend: str = "auto"
    # method-specific knobs for the baselines
    rank: int = 64                 # SparQ / Loki down-projection dim
    lim_layers: int = 2            # LessIsMore: score every k-th layer
    # ---- SelectionPlan knobs (core/plan.py) ----
    # selection granularity in tokens: 1 = per-token top-k (the paper's
    # Algorithm 1), >1 = block-granular CompactAttention-style selection on
    # a fixed grid (set to the paged pool's block size so materialising a
    # plan is a contiguous block-table sub-view, serving/pool.py).
    granularity: int = 1
    # cross-layer plan reuse: re-score every `reuse_interval` layers and
    # reuse the previous layer's plan in between (LessIsMore-style depth
    # amortisation, now first-class).  1 = score every layer (exact).
    reuse_interval: int = 1
    # global layer indices that ALWAYS re-score, breaking a reuse run
    # (periodic correction layers)
    correction_layers: Tuple[int, ...] = ()
    # low-rank scoring: project pre-aggregated queries and keys to this
    # dimension before the fused scoring kernel (Loki-style; a cached
    # deterministic projection stands in for offline PCA).  0 = full-dim.
    score_proj_dim: int = 0
    # gather-free fused selected attention: route block-granular selection
    # (granularity > 1) onto kernels/selected_attention.py, which streams
    # each selected KV slab straight from the unmaterialized cache instead
    # of materialize + attend (core/plan.py::fused_route has the full
    # dispatch rules; token plans, sliding windows, MLA and active meshes
    # stay on the staged path).
    fused_select_attn: bool = False
    # hierarchical KV pool (serving/pool.py): capacity of the host-memory
    # tier behind the device pool, in blocks.  0 = single-level pool
    # (pressure-eviction destroys cache entries); > 0 = eviction demotes
    # registered prefix blocks to pinned host buffers, admission matches
    # both tiers and promotes host hits back into fresh device blocks.
    host_tier_blocks: int = 0
    # max host-tier blocks the engine stages (async H2D) per serve step
    # ahead of their promotion, ranked by the QUOKA selection-count oracle
    # (serving/engine.py::_prefetch); 0 disables prefetch (promotions
    # fall back to copy-at-alloc).
    prefetch_depth: int = 4


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    # positional encoding
    use_rope: bool = True
    rope_theta: float = 10_000.0
    # sliding-window / local-global structure
    sliding_window: Optional[int] = None
    # repeating block pattern; None -> ("attn",) * n_layers collapsed to one
    # period.  e.g. gemma3: ("attn_local",)*5 + ("attn",)
    layer_pattern: Optional[Tuple[str, ...]] = None
    # explicit ((period, n_repeats), ...) override for non-periodic stacks,
    # e.g. deepseek-v3: ((("mla",), 3), (("mla_moe",), 58))
    layer_groups: Optional[Tuple[Tuple[Tuple[str, ...], int], ...]] = None
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    encoder: Optional[EncoderConfig] = None
    frontend: Optional[FrontendConfig] = None
    mtp: bool = False              # deepseek multi-token-prediction head
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    act: str = "silu"              # mlp activation ("silu"|"gelu"|"relu2")
    dtype: str = "bfloat16"
    # citation for the assigned-architecture pool
    source: str = ""
    # ---- runtime ----
    quoka: QuokaConfig = field(default_factory=QuokaConfig)
    remat: bool = False            # activation checkpointing in the stack
    max_seq_len: int = 131_072

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pattern(self) -> Tuple[str, ...]:
        if self.layer_pattern is not None:
            return self.layer_pattern
        base = "attn_moe" if self.moe is not None else "attn"
        if self.mla is not None:
            base = "mla_moe" if self.moe is not None else "mla"
        return (base,)

    def stacks(self) -> Sequence[Tuple[Tuple[str, ...], int]]:
        """Partition n_layers into (period, n_repeats) groups.

        Returns a list of period-stacks; the tail (n_layers % len(period))
        becomes its own single-repeat stack.
        """
        if self.layer_groups is not None:
            assert sum(len(p) * r for p, r in self.layer_groups) == self.n_layers
            return list(self.layer_groups)
        pat = self.pattern
        p = len(pat)
        reps, rem = divmod(self.n_layers, p)
        out = []
        if reps:
            out.append((pat, reps))
        if rem:
            out.append((pat[:rem], 1))
        return out

    def smoke(self, **overrides) -> "ModelConfig":
        """Reduced variant of the same family for CPU smoke tests."""
        ch = dict(
            n_layers=min(self.n_layers, 2),
            d_model=min(self.d_model, 256),
            n_heads=min(self.n_heads, 4),
            n_kv_heads=min(self.n_kv_heads, 2),
            d_ff=min(self.d_ff, 512),
            vocab=min(self.vocab, 512),
            head_dim=64 if self.resolved_head_dim >= 64 else self.resolved_head_dim,
            max_seq_len=4096,
            dtype="float32",
        )
        if self.n_kv_heads == self.n_heads:     # keep MHA archs MHA
            ch["n_kv_heads"] = ch["n_heads"]
        if self.layer_groups is not None:
            kinds = tuple(dict.fromkeys(
                k for pd, _ in self.layer_groups for k in pd))
            pat = kinds[:2] if len(kinds) >= 2 else kinds * 2
            ch["layer_groups"] = None
            ch["layer_pattern"] = pat
            ch["n_layers"] = len(pat)
        elif self.layer_pattern is not None:
            pat = self.layer_pattern[-ch["n_layers"]:]
            # keep at least one of each distinct block type if possible
            kinds = tuple(dict.fromkeys(self.layer_pattern))
            if len(kinds) > 1 and len(set(pat)) < len(kinds):
                pat = kinds[: ch["n_layers"]]
            while len(pat) < ch["n_layers"]:
                pat = pat + (pat[-1],)
            ch["layer_pattern"] = pat
            ch["n_layers"] = len(pat)
        if self.moe is not None:
            ch["moe"] = dataclasses.replace(
                self.moe, n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2),
                d_expert=min(self.moe.d_expert, 256), dispatch="dense")
        if self.mla is not None:
            ch["mla"] = MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                                  qk_nope_dim=32, qk_rope_dim=16,
                                  v_head_dim=32)
            ch["head_dim"] = 0
        if self.ssm is not None:
            ch["ssm"] = dataclasses.replace(self.ssm, d_state=16, head_dim=32)
        if self.rwkv is not None:
            ch["rwkv"] = RWKVConfig(head_dim=32, decay_lora=16)
        if self.encoder is not None:
            ch["encoder"] = dataclasses.replace(
                self.encoder, n_layers=2, n_ctx=64)
        if self.frontend is not None:
            ch["frontend"] = dataclasses.replace(
                self.frontend, n_tokens=16, d_in=min(self.frontend.d_in, 128))
        if self.sliding_window is not None:
            ch["sliding_window"] = min(self.sliding_window, 64)
        ch["quoka"] = dataclasses.replace(
            self.quoka, chunk_size=16, budget=32, n_queries=4, keep_first=2)
        ch.update(overrides)
        return dataclasses.replace(self, **ch)

    def param_count(self) -> int:
        """Analytic parameter count (approximate, for roofline MODEL_FLOPS)."""
        d, hd = self.d_model, self.resolved_head_dim
        nl = self.n_layers
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        per = 0
        counts = {}
        for kind in self.pattern:
            counts[kind] = counts.get(kind, 0) + 1
        pat = self.pattern
        reps = self.n_layers // len(pat) if len(pat) <= self.n_layers else 1
        total = emb
        # count per block kind over the real layer list
        layers = []
        for period, r in self.stacks():
            layers += list(period) * r
        for kind in layers:
            p = 0
            if kind in ("attn", "attn_local", "attn_moe", "enc_attn"):
                p += d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            if kind == "dec_cross":
                p += 2 * (d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d)
            if kind in ("mla", "mla_moe"):
                m = self.mla
                qk = m.qk_nope_dim + m.qk_rope_dim
                p += d * m.q_lora_rank + m.q_lora_rank * self.n_heads * qk
                p += d * (m.kv_lora_rank + m.qk_rope_dim)
                p += m.kv_lora_rank * self.n_heads * (m.qk_nope_dim + m.v_head_dim)
                p += self.n_heads * m.v_head_dim * d
            if kind in ("attn", "attn_local", "mla", "enc_attn", "dec_cross"):
                p += 3 * d * self.d_ff
            if kind in ("attn_moe", "mla_moe"):
                e = self.moe
                p += d * e.n_experts  # router
                p += e.n_experts * 3 * d * e.d_expert
                p += e.n_shared * 3 * d * (e.d_expert if self.mla else self.d_ff)
            if kind == "rwkv":
                p += 4 * d * d + d * self.d_ff * 2   # time-mix + channel-mix
            if kind in ("mamba", "mamba_shared_attn"):
                di = self.ssm.expand * d
                p += d * 2 * di + di * d + 2 * di * self.ssm.d_state
                if kind == "mamba_shared_attn":
                    pass  # shared block counted once below
            total += p
        if "mamba_shared_attn" in layers:
            total += d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            total += 3 * d * self.d_ff
        return int(total)

    def active_param_count(self) -> int:
        """Params active per token (MoE uses top_k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        full = self.param_count()
        n_moe_layers = sum(1 for pd, r in self.stacks() for k in pd * r
                           if k in ("attn_moe", "mla_moe"))
        inactive = n_moe_layers * (e.n_experts - e.top_k) * 3 * self.d_model * e.d_expert
        return int(full - inactive)


_REGISTRY = {}


def register(fn):
    """Decorator: register a zero-arg config factory under its module name."""
    name = fn.__module__.rsplit(".", 1)[-1].replace("_", "-")
    _REGISTRY[name] = fn
    return fn


def get_config(name: str) -> ModelConfig:
    from repro import configs as _c  # noqa: F401  (triggers registration)
    key = name.replace("_", "-").replace(".", "-")
    if key not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]()


def list_configs():
    from repro import configs as _c  # noqa: F401
    return sorted(_REGISTRY)
