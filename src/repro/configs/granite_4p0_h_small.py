"""Granite-4.0-H-Small [hf:ibm-granite/granite-4.0-h-small; hf].
A hybrid of 40 layers in a period of 10: nine Mamba-2 mixers (128 heads
of 64, state 128, one group, conv 4, chunk 256) and one GQA mixer (32/8
heads of 128, no position embedding, scores times 1/128) at index 5.
Every layer has a MoE of 72 SwiGLU experts of 768, 10 a token (softmax
over the kept router logits), plus a shared expert of 1,536.  Embedding
times 12, each residual branch times 0.22, logits over 16; tied
vocabulary of 100,352."""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    rope=False,
    num_experts=72,
    num_experts_per_tok=10,
    shared_expert_d_ff=1536,
    mlp_act="silu",
    mlp_gated=True,
    block_pattern=("ssm",) * 5 + ("attn",) + ("ssm",) * 4,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_ngroups=1,
    ssm_expand=2,
    ssm_chunk=256,
    conv1d_width=4,
    norm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
    source="hf:ibm-granite/granite-4.0-h-small (verified: hf config.json)",
))
