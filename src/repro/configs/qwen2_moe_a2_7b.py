"""qwen2-moe-a2.7b [moe] — 24L d_model=2048 16H (GQA kv=16) d_ff=1408
vocab=151936; MoE 60 routed experts top-4 + 4 shared experts.
[hf:Qwen/Qwen1.5-MoE-A2.7B; hf]"""

from ..models.config import ArchConfig, MoEConfig

CONFIG = ArchConfig(
    name="qwen2-moe-a2.7b", family="moe",
    n_layers=24, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=5632, vocab=151936, qkv_bias=True, rope_theta=1e6,
    moe=MoEConfig(n_experts=60, top_k=4, d_ff_expert=1408,
                  d_ff_shared=4 * 1408, every=1),
)


def reduced() -> ArchConfig:
    return CONFIG.with_(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        d_ff=128, vocab=512,
                        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32,
                                      d_ff_shared=2 * 32, every=1))
