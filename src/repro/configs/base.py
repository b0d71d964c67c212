"""Config dataclasses + the --arch registry."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense|moe|ssm|hybrid|encoder|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    moe_experts: int = 0
    moe_topk: int = 0
    norm: str = "rmsnorm"         # rmsnorm | layernorm_nonparam
    causal: bool = True
    frontend: str = "none"        # none | stub  (stub: precomputed embeds)
    rope_theta: float = 1e4
    rope: bool = True             # False: no positional encoding (NoPE)
    norm_eps: float = 1e-6
    # Granite's scalars: embeddings x embedding_multiplier, each residual
    # branch x residual_multiplier, logits / logits_scaling, attention
    # softmax scale attention_multiplier (None: 1/sqrt(head_dim))
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    tie_embeddings: bool = False  # the head is the embedding's transpose
    d_state: int = 16             # mamba state width
    attn_layer_period: int = 0    # jamba: 8
    attn_layer_offset: int = 4
    moe_layer_period: int = 0     # jamba: 2
    # the mixer of each layer of one period, when the pattern is neither
    # homogeneous nor jamba's ("attn" | "mamba2"); every layer then has
    # an MoE (granite-4.0-h: 9 mamba2 + 1 attn per period of 10)
    layer_types: Tuple[str, ...] = ()
    # Mamba-2 mixer: ssm_heads heads of ssm_head_dim channels (d_inner =
    # their product), B and C shared by the heads of each of ssm_groups
    # groups, chunked (SSD) prefill in chunks of ssm_chunk tokens
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_chunk: int = 256
    # one shared SwiGLU expert beside the routed ones (0: none)
    shared_d_ff: int = 0
    # (first, count) of the routed experts this chip holds; the router
    # keeps all moe_experts outputs.  None: every expert is held
    experts_held: Optional[Tuple[int, int]] = None
    moe_impl: str = "grouped"     # naive | lilac | grouped
    # MoE formulation used on the one-token decode path.  "grouped_flat"
    # (default) is the hand-written scatter dispatch; "naive_flat" emits
    # the canonical dense-dispatch einsum form so a lilac-compiled decode
    # step exposes the MoE to the detector (the serving tier uses this).
    moe_decode_impl: str = "grouped_flat"
    capacity_factor: float = 2.0
    kv_chunk: int = 1024
    remat: bool = True
    param_dtype: Any = jnp.bfloat16
    cache_dtype: Any = jnp.bfloat16
    source: str = ""              # provenance note ([arXiv/hf; tier])
    # distribution: when True, with_sharding_constraint is applied at
    # compute sites (TP-only weights inside the layer scan -> JIT per-layer
    # FSDP gathers). mesh_axis_sizes informs divisibility decisions.
    spmd_constraints: bool = False
    mesh_axis_sizes: tuple = ()   # (("data", 16), ("model", 16), ...)
    # gradient accumulation: activation memory scales 1/microbatches
    microbatches: int = 1
    # sequence-parallel activation carries between layers (§Perf lever):
    # shards the residual stream over the model axis, turning TP
    # all-reduces into all-gather/reduce-scatter pairs and dividing carry
    # memory by the model-axis size. True = optimized, False = the
    # Megatron-TP-style baseline.
    seq_parallel: bool = True
    # decode: shard the KV cache over the model axis on the SEQUENCE dim
    # when kv-heads are unshardable (MQA) — ring-style decode (§Perf lever)
    decode_cache_seq_shard: bool = False
    # MoE EP combine psum in bf16 instead of f32 (§Perf lever)
    moe_combine_bf16: bool = False

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


# The four LM shapes assigned to every architecture.
SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    import repro.configs  # noqa: F401  (ensures all configs imported)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_archs() -> Dict[str, ArchConfig]:
    import repro.configs  # noqa: F401
    return dict(_REGISTRY)


def shape_skips(cfg: ArchConfig, shape: ShapeConfig) -> Optional[str]:
    """Returns a skip reason or None (DESIGN.md §Arch-applicability)."""
    subquadratic = cfg.family in ("ssm", "hybrid")
    if shape.name == "long_500k" and not subquadratic:
        return "full-attention arch: 500k decode needs sub-quadratic mixer"
    if shape.kind == "decode" and not cfg.causal:
        return "encoder-only arch has no autoregressive decode step"
    return None


def smoke_config(cfg: ArchConfig) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    period = cfg.attn_layer_period or 1
    if cfg.layer_types:             # one whole period of the listed kinds
        cfg = cfg.replace(
            ssm_heads=8, ssm_head_dim=16, d_state=16, ssm_chunk=8,
            shared_d_ff=32 if cfg.shared_d_ff else 0,
            experts_held=(2, 4) if cfg.experts_held else None)
    return cfg.replace(
        n_layers=(len(cfg.layer_types) if cfg.layer_types
                  else 2 * period if period > 1 else 2),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads > 1 else 1,
        d_ff=128 if not cfg.moe_experts else 32,
        vocab=256,
        head_dim=16 if cfg.head_dim else None,
        moe_experts=min(cfg.moe_experts, 8) if cfg.moe_experts else 0,
        moe_topk=min(cfg.moe_topk, 2) if cfg.moe_topk else 0,
        kv_chunk=32,
        remat=False,
        param_dtype=jnp.float32,
        cache_dtype=jnp.float32,
    )
