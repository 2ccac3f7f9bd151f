"""olmoe-1b-7b [moe]: 16L d_model=2048 16H (MHA) d_ff(expert)=1024,
vocab=50304, MoE 64 experts top-8 without renormalising the top-8
weights, QK-norm, RMSNorm eps 1e-5, RoPE theta 1e4, 4096 positions, untied
head [arXiv:2409.02060; hf allenai/OLMoE-1B-7B-0924]."""
from repro.configs.base import ArchConfig, MoEConfig

CONFIG = ArchConfig(
    name="olmoe_1b_7b",
    family="moe",
    num_layers=16,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=0,                  # all FFN capacity lives in the experts
    vocab_size=50304,
    attention="full",
    rope_theta=10000.0,
    qk_norm=True,
    norm_eps=1e-5,
    moe=MoEConfig(num_experts=64, experts_per_token=8, d_ff_expert=1024,
                  norm_topk_prob=False),
    subquadratic=False,
)
