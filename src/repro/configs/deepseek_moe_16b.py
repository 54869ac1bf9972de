"""DeepSeekMoE-16B: fine-grained experts, 2 shared + 64 routed top-6.
[arXiv:2401.06066; hf deepseek-ai/deepseek-moe-16b-base config.json] 28L
d_model=2048 16H (MHA) head_dim=128 vocab=102400; layer 0 is a dense SwiGLU
of width 10944 (first_k_dense_replace=1), layers 1-27 are MoE with experts
of width 1408 and a softmax gate whose top-6 weights are not renormalised
(norm_topk_prob=false); rope_theta=1e4, rms_norm_eps=1e-6, untied head."""
from repro.configs.base import ModelConfig, MoEConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-moe-16b", family="moe",
    num_layers=28, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, head_dim=128,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, d_expert=1408,
                  first_dense=1, norm_topk=False),
    rope_theta=10000.0, norm_eps=1e-6,
    source="arXiv:2401.06066; hf",
))
