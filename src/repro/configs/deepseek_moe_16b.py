"""deepseek-moe-16b [moe]: 2 shared + 64 routed top-6, fine-grained experts.

28L d_model=2048 16H (kv=16) d_ff=1408(expert) vocab=102400, from the
published ``config.json`` of https://huggingface.co/deepseek-ai/deepseek-moe-16b-base
(DeepSeekMoE, arXiv:2401.06066).  Layer 0 is dense with d_ff=10944
(``first_k_dense_replace`` 1).  The gate is a softmax over all 64 experts
whose top 6 weights are used unnormalised (``scoring_func`` softmax,
``norm_topk_prob`` false, ``routed_scaling_factor`` 1); the two shared
experts form one always-on MLP of width 2 x 1408.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b",
    family="moe",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=128,
    d_ff=10944,             # dense first layer
    vocab_size=102400,
    n_experts=64,
    n_shared_experts=2,
    experts_per_token=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    moe_norm_topk=False,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    source="https://huggingface.co/deepseek-ai/deepseek-moe-16b-base",
)
