"""granite-4.0-h-small [hybrid, moe]: Mamba-2 / attention hybrid, 72-expert MoE.

40L d_model=4096, vocab=100352, tied [hf:ibm-granite/granite-4.0-h-small,
config.json].  The layer types repeat with a period of 10: five Mamba-2
layers, one attention layer, four Mamba-2 layers (attention at 5, 15, 25,
35).  Mamba-2: 128 heads x 64, d_state 128, 1 group, conv 4 with bias,
chunk 256.  Attention: GQA 32 query / 8 KV heads of 128, no position
embedding (NoPE), softmax scale ``attention_multiplier`` 1/128.  Every FFN
is an MoE: 72 routed SwiGLU experts of width 768 (``intermediate_size``, read
as the expert width), 10 per token with the softmax over the top 10 router
logits, beside a shared SwiGLU expert of width 1536.  Embeddings x 12, each
block output x 0.22 before its residual add, logits / 16; RMSNorm eps 1e-5.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    vocab_size=100_352,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    position_embedding="none",
    attention_multiplier=0.0078125,
    activation="swiglu",
    pattern=("ssd:moe",) * 5 + ("attn:moe",) + ("ssd:moe",) * 4,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    num_experts=72,
    moe_top_k=10,
    moe_d_ff=768,
    shared_expert=True,
    shared_d_ff=1536,
    moe_router="topk_softmax",
    ssd_state=128,
    ssd_headdim=64,
    ssd_expand=2,
    ssd_ngroups=1,
    ssd_chunk=256,
    conv_width=4,
)
