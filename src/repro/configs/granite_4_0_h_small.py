"""Granite 4.0-H Small (32B-A9B) — Mamba-2/attention hybrid with routed and
shared experts [huggingface.co/ibm-granite/granite-4.0-h-small, config.json].

Published: 40 layers at hidden 4096, 36 Mamba-2 mixers (128 heads of 64,
state 128, one group, conv 4, expand 2, chunk 256) and 4 NoPE GQA mixers
(32 query / 8 KV heads of 128) at layers 5, 15, 25, 35; every layer's
channel mixer is a MoE of 72 SwiGLU experts of width 768, top-10 (softmax
over the top-10 logits), beside a shared SwiGLU expert of width 1536.
Multipliers: embedding x12, residual branches x0.22, attention scale 1/128,
logits / 16.  Vocabulary 100,352, tied.

``CONFIG`` is one chip's share of a 16-chip deployment: two pipeline stages
of 20 layers, 8-way expert parallelism inside each.  This chip is stage 1,
expert shard 0: layers 0-19 (two whole periods of 9 Mamba-2 layers and one
attention layer) and experts 0-8 of each layer's 72.  The router keeps its
72 outputs and top-10; every width is as published.  Weights are bfloat16,
the checkpoint's dtype (4.42 B parameters, 8.84 GB).
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=20, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=768,
    vocab=100352, head_dim=128,
    n_experts=72, top_k=10, moe_every=1, shared_ff=1536,
    experts_held=9, expert_first=0,
    ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_chunk=256, ssm_conv=4,
    attn_every=10, attn_pos=5,
    use_rope=False, tie_embeddings=True,
    embedding_mult=12.0, residual_mult=0.22, attn_scale=0.0078125,
    logits_div=16.0,
    param_dtype="bfloat16",
)

SMOKE = ArchConfig(
    name="granite-4.0-h-smoke", family="hybrid",
    n_layers=6, d_model=64, n_heads=4, n_kv_heads=2, d_ff=32,
    vocab=256, head_dim=16,
    n_experts=8, top_k=3, moe_every=1, shared_ff=48,
    experts_held=4, expert_first=2,
    ssm_state=16, ssm_expand=2, ssm_head_dim=16, ssm_chunk=8, ssm_conv=4,
    attn_every=3, attn_pos=1,
    use_rope=False, tie_embeddings=True,
    embedding_mult=12.0, residual_mult=0.22, attn_scale=0.0625,
    logits_div=16.0, loss_chunk=32,
)
