"""Architecture configuration schema.

One `ArchConfig` describes any of the 10 assigned architectures; the
families map to model builders:

* dense/moe/ssm/hybrid/vlm — decoder-only LM (`models.lm`), where `vlm`
  prepends stub patch embeddings;
* audio — encoder-decoder (`models.encdec`) with a stub conv frontend
  (precomputed frame embeddings).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    d_ff_shared: int = 0         # width of the always-on shared SwiGLU; 0: none
    every: int = 1               # MoE replaces the MLP every `every` layers
    capacity_factor: float = 1.25
    router_z_coef: float = 1e-3  # router z-loss (stability)
    aux_coef: float = 1e-2       # load-balancing auxiliary loss
    # expert parallelism: the layer holds rank `expert_rank`'s contiguous
    # share of the `n_experts` the router scores; 1 rank holds them all
    expert_ranks: int = 1
    expert_rank: int = 0

    @property
    def n_held(self) -> int:
        return self.n_experts // self.expert_ranks

    @property
    def first_held(self) -> int:
        return self.expert_rank * self.n_held


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    d_conv: int = 4
    chunk: int = 128


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | hybrid | vlm | audio | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None  # default d_model // n_heads
    qk_norm: bool = False        # qwen3
    qkv_bias: bool = False       # qwen2/2.5
    window: int | None = None    # sliding-window attention (danube)
    rope_theta: float = 1e4
    rope: bool = True            # False: no position embedding (NoPE)
    attn_scale: float | None = None   # score scale; default head_dim**-0.5
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # scalar multipliers (granite): the embedding's output, every sublayer's
    # output before it joins the residual, and the logits (divided)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    # hybrid (jamba): attention layer when (i % attn_period) == attn_offset,
    # else mamba; 0 disables (pure attention)
    attn_period: int = 0
    attn_offset: int = 0
    # encoder-decoder (whisper)
    enc_layers: int = 0
    enc_seq: int = 1500          # whisper-tiny frame positions (stubbed)
    max_decoder_positions: int = 0   # 0 = unlimited (RoPE); whisper uses 448
    # modality frontend stubs: number of prepended embedding tokens
    frontend: str | None = None  # None | "patch" | "audio"
    n_frontend_tokens: int = 0
    # execution knobs
    attn_impl: str = "jnp"       # jnp | pallas | pallas_interpret
    dtype: str = "bfloat16"
    remat: str = "full"          # none | full | dots
    scan_layers: bool = True

    def with_(self, **kw) -> "ArchConfig":
        return replace(self, **kw)

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    def is_attn_layer(self, i: int) -> bool:
        if self.attn_period == 0:
            return self.family != "ssm"
        return (i % self.attn_period) == self.attn_offset

    def is_moe_layer(self, i: int) -> bool:
        return self.moe is not None and (i % self.moe.every
                                         == self.moe.every - 1)

    def supports_long_context(self) -> bool:
        """Sub-quadratic decode: SSM, hybrid, or sliding-window attention."""
        return (self.family in ("ssm", "hybrid")
                or self.window is not None)

    def param_count(self) -> int:
        """Analytic parameter count (total, incl. all experts)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        hd = self.resolved_head_dim
        qkv = d * hd * self.n_heads + 2 * d * hd * self.n_kv_heads \
            + hd * self.n_heads * d
        if self.qkv_bias:
            qkv += hd * (self.n_heads + 2 * self.n_kv_heads)
        mlp_dense = 3 * d * ff
        total = 0
        for i in range(self.n_layers):
            attn = self.is_attn_layer(i)
            if attn:
                total += qkv + 2 * d  # mixer + 2 norms
            elif self.ssm is not None:
                s = self.ssm
                d_in = s.expand * d
                nh = d_in // s.head_dim
                total += d * (2 * d_in + 2 * s.d_state + nh) \
                    + d_in * d + s.d_conv * (d_in + 2 * s.d_state) \
                    + 2 * nh + 2 * d
            if self.family == "ssm":
                continue  # mamba2 has no separate MLP
            if self.is_moe_layer(i):
                m = self.moe
                total += d * m.n_experts + 3 * d * (
                    m.d_ff_expert * m.n_held + m.d_ff_shared)
            else:
                total += mlp_dense
            total += d  # ffn norm
        total += v * d * (1 if self.tie_embeddings else 2) + d
        if self.enc_layers:
            total += self.enc_layers * (qkv + mlp_dense + 3 * d) \
                + self.enc_seq * d
            # decoder cross-attention
            total += self.n_layers * (qkv + d)
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k + shared experts only;
        of a share of the experts, the expected top_k * held / n_experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full = self.param_count()
        n_moe_layers = sum(1 for i in range(self.n_layers)
                           if self.is_moe_layer(i))
        inactive = (m.n_held - m.top_k * m.n_held / m.n_experts) * 3 \
            * self.d_model * m.d_ff_expert * n_moe_layers
        return round(full - inactive)


@dataclass(frozen=True)
class ShapeConfig:
    """One benchmark cell: (sequence length, global batch, step kind)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                    # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
