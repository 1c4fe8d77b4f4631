"""granite-4.0-h-small — Mamba-2/attention hybrid with 72 fine-grained
experts after every mixer
[hf:ibm-granite/granite-4.0-h-small, config.json].

40 layers in periods of ten (Mamba-2 x5, attention, Mamba-2 x4), each
with its own weights and its own MoE block: 72 experts of width 768,
top-10 (a softmax over the 10 chosen logits), plus one shared expert of
width 1536. Attention is GQA 32/8 of 128 with no positional encoding
and scores scaled by ``attention_multiplier`` (1/128, not 1/sqrt(128)).
Embeddings are scaled by 12, every residual branch by 0.22, logits
divided by 16. RMSNorm eps 1e-5, tied embeddings, bf16.
"""
from repro.configs.base import ARCHITECTURES, ATTN, MAMBA, ModelConfig


@ARCHITECTURES.register("granite-4.0-h-small")
def granite_4_0_h_small() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-small",
        family="hybrid",
        source="hf:ibm-granite/granite-4.0-h-small (config.json)",
        num_layers=40,
        d_model=4096,
        vocab_size=100352,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,  # 32 * 128 == 4096
        use_rope=False,  # position_embedding_type: nope
        attn_scale=0.0078125,  # attention_multiplier
        d_ff=768,  # intermediate_size: the width of one expert
        num_experts=72,
        experts_per_token=10,
        moe_capacity_factor=0.0,  # dropless
        shared_expert_ff=1536,  # shared_intermediate_size
        ssm_state_size=128,
        ssm_expand=2,  # d_inner 8192
        ssm_head_dim=64,  # 128 Mamba-2 heads, n_groups 1
        ssm_conv_width=4,
        ssm_chunk=256,  # mamba_chunk_size
        block_pattern=(MAMBA,) * 5 + (ATTN,) + (MAMBA,) * 4,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        norm_eps=1e-5,
        tie_embeddings=True,
    )
