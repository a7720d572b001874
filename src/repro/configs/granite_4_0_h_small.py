"""Granite-4.0-H-Small (IBM, 32B-A9B; huggingface.co/ibm-granite/
granite-4.0-h-small, config.json): 40 layers, d=4096.  Layers 5, 15, 25 and
35 mix with GQA attention (32/8 heads of 128, no position embedding, scores
scaled by attention_multiplier 1/128); the other 36 with Mamba-2 (128 heads
of 64, state 128, one B/C group, conv 4, chunk 256, expand 2, norm after the
gate).  Every layer ends in a MoE: 72 routed SwiGLU experts of width 768,
top-10, gates the softmax of the chosen logits, plus a shared SwiGLU expert
of width 1536.  μP multipliers: embedding ×12, both residual branches
×0.22, logits ÷16.  RMSNorm eps 1e-5; vocab 100352, head tied."""

from repro.config import ModelConfig, register_arch


@register_arch("granite-4.0-h-small")
def granite_4_0_h_small() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-small",
        family="ssm-moe",
        num_layers=40,
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        d_ff=768,
        vocab_size=100_352,
        mlp_activation="swiglu",
        norm_type="rmsnorm",
        norm_eps=1e-5,
        use_rope=False,
        attention_multiplier=1.0 / 128,
        layer_pattern="MMMMMGMMMM",
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logit_scale=1.0 / 16,
        tie_embeddings=True,
        num_experts=72,
        num_experts_per_tok=10,
        shared_d_ff=1536,
        ssm_state=128,
        ssm_head_dim=64,
        ssm_expand=2,
        ssm_chunk=256,
        ssm_conv_width=4,
        ssm_ffn=True,
    )
