"""granite-4.0-h-small: weights made from a seed, the plain reference forward
pass, and the work of one decode step, all from the sizes in
``granite-4.0-h-small.json``.

The reference follows the published GraniteMoeHybrid description (its
config.json): the tokens' embedding times ``embedding_multiplier``; per
layer, pre-norm RMSNorm, a mixer (Mamba-2 or attention, as ``layer_types``
says), its output times ``residual_multiplier`` added to the residual, then
pre-norm RMSNorm, the MoE and its output times ``residual_multiplier``
added; a final RMSNorm, the tied output embedding, and the logits divided
by ``logits_scaling``.

- Attention: grouped-query causal attention with no position embedding
  (``position_embedding_type`` "nope") and scores times
  ``attention_multiplier`` (not 1/sqrt(head_dim)); no biases.
- Mamba-2: one input projection to [z, x, B, C, dt], a causal depthwise
  convolution with bias and SiLU over [x, B, C], the recurrence
  h_t = exp(-exp(A_log) dt_t) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t + D x_t
  with dt_t = softplus(dt_raw + dt_bias) and one B/C group, the gate
  y * silu(z), RMSNorm over the inner width, the output projection.  It
  runs token by token.
- MoE: the router scores every one of the routed experts (``num_local_experts``
  held here times ``expert_parallel_size``); the ``num_experts_per_tok``
  highest are chosen and their gates are the softmax of the chosen scores.
  The layer adds the gated SwiGLU of each chosen expert that this chip holds
  (experts ``expert_rank * num_local_experts`` onwards) and the shared
  SwiGLU of width ``shared_intermediate_size``.  What the experts held on
  other chips would add is left out, as it is in the program: that is the
  cut the JSON file states, not a departure.

It runs in float32 at full matmul precision and imports nothing of the
program; the parameter layout it reads is the program's, made here and
checked against the program's own shapes by the harness.  Each layer's
weights are cast to float32 inside the layer loop, one layer at a time,
so the whole tree is never held in float32 beside its bf16 copy.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp


def _sizes(c: dict) -> SimpleNamespace:
    d, nh = c["hidden_size"], c["num_attention_heads"]
    d_in = c["mamba_expand"] * d
    held = c["num_local_experts"]
    nl, stage = c["num_hidden_layers"], c["pipeline_stage"]
    kinds = c["layer_types"][stage * nl:(stage + 1) * nl]
    attn = [i for i, k in enumerate(kinds) if k == "attention"]
    period = attn[1] - attn[0] if len(attn) > 1 else nl
    return SimpleNamespace(
        d=d, nl=nl, nh=nh, nkv=c["num_key_value_heads"], hd=d // nh,
        d_in=d_in, mh=c["mamba_n_heads"], mp=c["mamba_d_head"],
        n=c["mamba_d_state"], kc=c["mamba_d_conv"],
        conv_ch=d_in + 2 * c["mamba_d_state"],
        held=held, routed=held * c["expert_parallel_size"],
        first=c["expert_rank"] * held, k=c["num_experts_per_tok"],
        fe=c["intermediate_size"], fs=c["shared_intermediate_size"],
        v=c["vocab_size"], kinds=kinds, period=period,
        n_attn=len(attn), n_ssm=nl - len(attn))


def program_arch(c: dict, arch):
    """The program's config cut as `c` states: its registry config is the
    published model (40 layers, all 72 experts held); this chip runs
    `num_hidden_layers` of them and holds `num_local_experts` of the
    routed experts, the share of rank `expert_rank` of
    `expert_parallel_size`."""
    from dataclasses import replace
    return arch.with_(n_layers=c["num_hidden_layers"],
                      moe=replace(arch.moe,
                                  expert_ranks=c["expert_parallel_size"],
                                  expert_rank=c["expert_rank"]))


def program_mismatches(c: dict, arch) -> list[str]:
    """Where the program's config differs from `c`."""
    z = _sizes(c)
    m, s = arch.moe, arch.ssm
    have = {
        "hidden_size": arch.d_model, "num_hidden_layers": arch.n_layers,
        "num_attention_heads": arch.n_heads,
        "num_key_value_heads": arch.n_kv_heads,
        "head_dim": arch.resolved_head_dim, "vocab_size": arch.vocab,
        "rms_norm_eps": arch.norm_eps,
        "tie_word_embeddings": arch.tie_embeddings,
        "position_embedding_type": "rope" if arch.rope else "nope",
        "attention_multiplier": arch.attn_scale,
        "embedding_multiplier": arch.embedding_multiplier,
        "residual_multiplier": arch.residual_multiplier,
        "logits_scaling": arch.logits_scaling,
        "attention_layers": [i for i in range(arch.n_layers)
                             if arch.is_attn_layer(i)],
        "moe_layers": sum(arch.is_moe_layer(i) for i in range(arch.n_layers)),
        "routed_experts": m.n_experts if m else None,
        "num_local_experts": m.n_held if m else None,
        "first_held": m.first_held if m else None,
        "num_experts_per_tok": m.top_k if m else None,
        "intermediate_size": m.d_ff_expert if m else None,
        "shared_intermediate_size": m.d_ff_shared if m else None,
        "mamba_d_state": s.d_state if s else None,
        "mamba_d_head": s.head_dim if s else None,
        "mamba_expand": s.expand if s else None,
        "mamba_d_conv": s.d_conv if s else None,
        "mamba_chunk_size": s.chunk if s else None,
        "mamba_n_heads": s.expand * arch.d_model // s.head_dim if s else None,
        "torch_dtype": arch.dtype,
    }
    want = dict(c, head_dim=z.hd, moe_layers=z.nl, routed_experts=z.routed,
                first_held=z.first,
                attention_layers=[i for i, k in enumerate(z.kinds)
                                  if k == "attention"])
    bad = [f"{k}: program {v!r}, config {want[k]!r}"
           for k, v in have.items() if want[k] != v]
    if c["mamba_n_groups"] != 1:
        bad.append(f"mamba_n_groups: program 1, config "
                   f"{c['mamba_n_groups']!r}")
    return bad


def make_params(c: dict, key) -> dict:
    """The served weights (bf16; the router and the decay, skip and
    step-bias vectors in float32, as the program keeps them), in the
    program's layout: `period` slots, each stacked over the periods.  Each
    matrix is drawn with std fan_in**-0.5; the residual multiplier, not a
    depth factor, scales what each sublayer adds."""
    z = _sizes(c)
    dt = jnp.dtype(c["torch_dtype"])
    f32 = jnp.float32
    npd = z.nl // z.period
    ks = iter(jax.random.split(key, 32 * z.period + 4))

    def normal(shape, std):
        return jax.random.normal(next(ks), (npd,) + shape, f32) * std

    def dense(shape, fan_in):
        return normal(shape, fan_in ** -0.5).astype(dt)

    def scale(shape):
        return (1.0 + normal(shape, 0.1)).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), (npd,) + shape, f32, lo, hi)

    def mamba():
        step = jnp.exp(uniform((z.mh,), jnp.log(1e-3), jnp.log(1e-1)))
        return {"in_proj": dense((z.d, 2 * z.d_in + 2 * z.n + z.mh), z.d),
                "conv_w": dense((z.kc, z.conv_ch), z.kc),
                "conv_b": normal((z.conv_ch,), 0.1).astype(dt),
                "A_log": jnp.log(uniform((z.mh,), 1.0, 16.0)),
                "D": 1.0 + normal((z.mh,), 0.1),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                "norm": {"scale": scale((z.d_in,))},
                "out_proj": dense((z.d_in, z.d), z.d_in)}

    def attention():
        return {"wq": dense((z.d, z.nh * z.hd), z.d),
                "wk": dense((z.d, z.nkv * z.hd), z.d),
                "wv": dense((z.d, z.nkv * z.hd), z.d),
                "wo": dense((z.nh * z.hd, z.d), z.nh * z.hd)}

    def moe():
        return {"router": normal((z.d, z.routed), z.d ** -0.5),
                "w_gate": dense((z.held, z.d, z.fe), z.d),
                "w_up": dense((z.held, z.d, z.fe), z.d),
                "w_down": dense((z.held, z.fe, z.d), z.fe),
                "shared": {"w_gate": dense((z.d, z.fs), z.d),
                           "w_up": dense((z.d, z.fs), z.d),
                           "w_down": dense((z.fs, z.d), z.fs)}}

    slots = {}
    for i in range(z.period):
        mixer = ({"attn": attention()} if z.kinds[i] == "attention"
                 else {"ssm": mamba()})
        slots[f"slot{i}"] = {"mixer_norm": {"scale": scale((z.d,))},
                             **mixer,
                             "ffn_norm": {"scale": scale((z.d,))},
                             "moe": moe()}
    # the embedded token (times 12) stays small beside what the layers add
    # to the residual: with the output embedding tied, a larger table makes
    # every position predict its own input token, whatever the layers do
    table = (jax.random.normal(next(ks), (z.v, z.d), f32)
             * (0.05 * z.d ** -0.5)).astype(dt)
    final = (1.0 + 0.1 * jax.random.normal(next(ks), (z.d,), f32)).astype(dt)
    return {"periods": slots, "final_norm": {"scale": final},
            "embed": {"table": table}}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(w, h, act):
    return act(jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def forward(c: dict, params: dict, tokens, act=None) -> jax.Array:
    """Logits (S, V) in float32 of every position of `tokens` (S,).  `act`,
    where given, rounds the input of every matmul (the fp8 control)."""
    z = _sizes(c)
    act = act or (lambda x: x)
    eps = c["rms_norm_eps"]
    mult = c["residual_multiplier"]
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def attention(a, h):
        q = (h @ a["wq"]).reshape(s, z.nh, z.hd)
        k = (h @ a["wk"]).reshape(s, z.nkv, z.hd)
        val = (h @ a["wv"]).reshape(s, z.nkv, z.hd)
        k = jnp.repeat(k, z.nh // z.nkv, axis=1)       # query head i reads
        val = jnp.repeat(val, z.nh // z.nkv, axis=1)   # key head i // group
        sc = jnp.einsum("qhd,khd->hqk", q, k) * c["attention_multiplier"]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), val)
        return act(o.reshape(s, z.nh * z.hd)) @ a["wo"]

    def mamba(m, h):
        proj = h @ m["in_proj"]
        zg = proj[:, :z.d_in]
        xbc = proj[:, z.d_in:2 * z.d_in + 2 * z.n]
        dt_raw = proj[:, 2 * z.d_in + 2 * z.n:]
        padded = jnp.concatenate([jnp.zeros((z.kc - 1, z.conv_ch)), xbc])
        conv = sum(padded[i:i + s] * m["conv_w"][i] for i in range(z.kc))
        xbc = jax.nn.silu(conv + m["conv_b"])
        xs, b, cm = (xbc[:, :z.d_in], xbc[:, z.d_in:z.d_in + z.n],
                     xbc[:, z.d_in + z.n:])
        step = jax.nn.softplus(dt_raw + m["dt_bias"])             # (S, H)
        decay = jnp.exp(-jnp.exp(m["A_log"]) * step)               # (S, H)
        xh = xs.reshape(s, z.mh, z.mp)

        def token(state, inp):                                     # (H, N, P)
            a_t, b_t, c_t, x_t, dt_t = inp
            state = (a_t[:, None, None] * state
                     + dt_t[:, None, None] * b_t[None, :, None]
                     * x_t[:, None, :])
            return state, jnp.einsum("n,hnp->hp", c_t, state)

        _, y = jax.lax.scan(token, jnp.zeros((z.mh, z.n, z.mp)),
                            (decay, b, cm, xh, step))
        y = (y + m["D"][None, :, None] * xh).reshape(s, z.d_in)
        y = act(_rms(y * jax.nn.silu(zg), m["norm"]["scale"], eps))
        return y @ m["out_proj"]

    def moe(m, h):
        top, ids = jax.lax.top_k(h @ m["router"], z.k)             # (S, k)
        gates = jax.nn.softmax(top, -1)
        held = z.first + jnp.arange(z.held)
        w = jnp.sum(jnp.where(ids[:, :, None] == held, gates[:, :, None],
                              0.0), axis=1)                        # (S, held)
        g = jnp.einsum("sd,edf->sef", h, m["w_gate"])
        u = jnp.einsum("sd,edf->sef", h, m["w_up"])
        y = jnp.einsum("sef,efd->sed", act(jax.nn.silu(g) * u), m["w_down"])
        return jnp.einsum("se,sed->sd", w, y) + _swiglu(m["shared"], h, act)

    def period(x, pp):
        for i in range(z.period):
            # the barrier keeps each layer's cast after the layer before it
            x, lp = jax.lax.optimization_barrier((x, pp[f"slot{i}"]))
            lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
            h = act(_rms(x, lp["mixer_norm"]["scale"], eps))
            y = (attention(lp["attn"], h) if z.kinds[i] == "attention"
                 else mamba(lp["ssm"], h))
            x = x + mult * y
            h = act(_rms(x, lp["ffn_norm"]["scale"], eps))
            x = x + mult * moe(lp["moe"], h)
        return x, None

    with jax.default_matmul_precision("highest"):
        table = params["embed"]["table"]
        x = table[tokens].astype(jnp.float32) * c["embedding_multiplier"]
        x, _ = jax.lax.scan(period, x, params["periods"])
        x = act(_rms(x, params["final_norm"]["scale"].astype(jnp.float32),
                     eps))
        return (x @ table.astype(jnp.float32).T) / c["logits_scaling"]


# ---------------------------------------------------------------------------
# work of one decode step (batch 1), from the shapes alone
# ---------------------------------------------------------------------------
def _mamba_params(z) -> int:
    return (z.d * (2 * z.d_in + 2 * z.n + z.mh) + z.kc * z.conv_ch
            + z.conv_ch + 3 * z.mh + z.d_in + z.d_in * z.d)


def _attn_params(z) -> int:
    return 2 * z.d * z.nh * z.hd + 2 * z.d * z.nkv * z.hd


def _expert_params(z) -> int:
    return 3 * z.d * z.fe


def _other_params(z) -> int:
    """Per layer, besides the mixer and the held experts: the router, the
    shared expert and the two norms."""
    return z.d * z.routed + 3 * z.d * z.fs + 2 * z.d


def param_count(c: dict) -> int:
    z = _sizes(c)
    return (z.n_ssm * _mamba_params(z) + z.n_attn * _attn_params(z)
            + z.nl * (z.held * _expert_params(z) + _other_params(z))
            + z.v * z.d + z.d)


def _f32_params(z) -> int:
    """Parameters the program keeps in float32: the router and each Mamba
    layer's decay, skip and step-bias vectors."""
    return z.nl * z.d * z.routed + z.n_ssm * 3 * z.mh


def param_bytes(c: dict) -> int:
    z = _sizes(c)
    return param_count(c) * 2 + _f32_params(z) * 2


def expert_bytes(c: dict) -> int:
    """Bytes of one expert of one layer (bf16)."""
    return _expert_params(_sizes(c)) * 2


def routed_per_layer(c: dict) -> float:
    """Held experts a token is expected to route to in one layer, at even
    routing: top_k x held / routed (1.25 of the 9 held)."""
    z = _sizes(c)
    return z.k * z.held / z.routed


def state_bytes(c: dict) -> int:
    """Decode state of one sequence besides the K/V: each Mamba layer's
    SSM state (f32) and its last `d_conv - 1` convolution inputs (bf16)."""
    z = _sizes(c)
    return z.n_ssm * (z.mh * z.n * z.mp * 4 + (z.kc - 1) * z.conv_ch * 2)


def kv_bytes_per_position(c: dict) -> int:
    z = _sizes(c)
    return z.n_attn * 2 * z.nkv * z.hd * 2              # K and V, bf16


def decode_bytes(c: dict, cache_len: int) -> int:
    """Least HBM bytes of the step at position `cache_len`, the same
    whatever implements the MoE: every parameter but the held experts once
    (the output head reads the whole table), the held experts a token is
    expected to route to, the state read and written, the K/V of the
    `cache_len` positions before it read and its own written."""
    z = _sizes(c)
    experts = z.nl * z.held * expert_bytes(c)
    return (param_bytes(c) - experts
            + round(z.nl * routed_per_layer(c) * expert_bytes(c))
            + 2 * state_bytes(c)
            + kv_bytes_per_position(c) * (cache_len + 1))


def decode_flops(c: dict, cache_len: int) -> int:
    """Matmul and state operations of the step: the mixers' projections,
    the router, the shared expert, the held experts a token is expected to
    route to, the output head, attention over `cache_len + 1` positions,
    the convolution and the recurrence (3 per state element to update it,
    2 to read it out)."""
    z = _sizes(c)
    weights = (z.n_ssm * z.d * (2 * z.d_in + 2 * z.n + z.mh + z.d_in)
               + z.n_attn * _attn_params(z)
               + z.nl * (z.d * z.routed + 3 * z.d * z.fs
                         + routed_per_layer(c) * _expert_params(z))
               + z.v * z.d)
    attn = z.n_attn * 2 * z.nh * z.hd * (cache_len + 1)
    ssm = z.n_ssm * (2 * z.kc * z.conv_ch + 5 * z.mh * z.n * z.mp)
    return round(2 * (weights + attn) + ssm)
