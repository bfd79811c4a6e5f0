"""granite-4.0-h-small [hybrid] — 40L d_model=4096, Mamba-2 mixers with a
NoPE GQA attention layer at slot 5 of every 10 (32H, kv=8, head_dim 128,
scores x 1/128), every layer an MoE of 72 experts (width 768) top-10 plus
one shared SwiGLU of width 1536; embedding x 12, every sublayer output
x 0.22 into the residual, logits / 16; vocab=100352, tied embeddings.
[hf:ibm-granite/granite-4.0-h-small config.json (granitemoehybrid)]"""

from ..models.config import ArchConfig, MoEConfig, SSMConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8,
    d_ff=0, vocab=100352, rope=False, attn_scale=0.0078125,
    norm_eps=1e-5, tie_embeddings=True,
    attn_period=10, attn_offset=5,
    moe=MoEConfig(n_experts=72, top_k=10, d_ff_expert=768, d_ff_shared=1536,
                  every=1),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, d_conv=4, chunk=256),
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
)


def reduced() -> ArchConfig:
    return CONFIG.with_(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                        vocab=512, attn_period=4, attn_offset=2,
                        attn_scale=1 / 16,
                        moe=MoEConfig(n_experts=16, top_k=4, d_ff_expert=32,
                                      d_ff_shared=64, every=1),
                        ssm=SSMConfig(d_state=16, head_dim=16, expand=2,
                                      d_conv=4, chunk=32))
