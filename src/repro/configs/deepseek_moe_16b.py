"""deepseek-moe-16b — fine-grained MoE: 2 shared + 64 routed top-6.

28L d_model=2048 16H (kv=16, i.e. MHA, head_dim 128) vocab=102400 untied.
Layer 0 is a dense SwiGLU of width 10944 (``intermediate_size``,
``first_k_dense_replace`` 1); layers 1-27 are MoE: 64 routed experts of
width 1408 (``moe_intermediate_size``), 6 a token, and 2 shared experts
(one SwiGLU of width 2816).  The softmax gate's top-6 weights are not
renormalised (``norm_topk_prob`` false).  rope theta 10000, RMSNorm eps
1e-6, no qk-norm.
[arXiv:2401.06066; hf:deepseek-ai/deepseek-moe-16b-base]
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b",
    family="moe",
    num_layers=28,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    head_dim=128,
    rope_theta=10000.0,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_ff_expert=1408,
        num_shared_experts=2,
        first_dense_layers=1,
        d_ff_dense=10944,
        norm_topk_prob=False,
    ),
)


def smoke_config() -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    return CONFIG.replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=256,
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=96,
                      num_shared_experts=1, first_dense_layers=1,
                      d_ff_dense=128, norm_topk_prob=False),
    )
