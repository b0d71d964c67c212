"""granite-4.0-h-small — Mamba-2 + attention 9:1, MoE 72e top-10 + shared.
[hybrid] 40L d_model=4096; per period of 10: 9 Mamba-2 (128 heads x 64,
d_state 128, 1 group, conv 4, chunk 256) and 1 NoPE GQA attention (32H,
kv=8, head_dim 128, softmax scale 1/128); every layer an MoE of 72 experts
of width 768, top-10, plus one shared SwiGLU expert of width 1536;
embeddings x12, residual branches x0.22, logits /16, tied head, RMSNorm
eps 1e-5, vocab 100352.
[hf:ibm-granite/granite-4.0-h-small config.json; hf]
"""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=768,
    vocab=100352,
    moe_experts=72,
    moe_topk=10,
    layer_types=("mamba2",) * 5 + ("attn",) + ("mamba2",) * 4,
    d_state=128,
    ssm_heads=128,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_chunk=256,
    shared_d_ff=1536,
    rope=False,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    tie_embeddings=True,
    source="[hf:ibm-granite/granite-4.0-h-small; hf]",
))
