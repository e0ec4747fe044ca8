"""granite-4.0-h-micro — hybrid Mamba-2 + NoPE GQA, Granite's multipliers.

[hf:ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid]  40L d_model=2048; Mamba-2 mixers except attention at
layers 5, 15, 25, 35 (32H, GQA kv=8, head_dim 64, no positional embedding,
softmax scale 0.015625); a SwiGLU MLP of 8192 after every mixer (no
experts); Mamba-2 with 64 heads of 64, d_state 128, one group, conv 4, chunk
256; embedding x12, residual branches x0.22, logits /8; tied embeddings,
vocab=100352.
"""
from repro.configs.base import (FF_SWIGLU, SSM, ModelConfig, SSMConfig,
                                register)


@register("granite-4.0-h-micro")
def granite_4_0_h_micro() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro",
        family="hybrid",
        num_layers=40,
        d_model=2048,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab_size=100_352,
        default_mixer=SSM,
        attn_every=10,
        attn_offset=5,
        ff_kind=FF_SWIGLU,
        ssm=SSMConfig(d_state=128, head_dim=64, expand=2, num_groups=1,
                      conv_width=4, chunk=256),
        position_embedding_type="nope",
        attention_multiplier=0.015625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        norm_eps=1e-5,
        tie_embeddings=True,
        supports_long_context=True,
        expected_params=3.19e9,
        source="hf:ibm-granite/granite-4.0-h-micro",
    )
