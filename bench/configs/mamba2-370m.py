"""mamba2-370m: weights made from a seed, the plain reference forward pass,
and the work of one decode step, all from the sizes in ``mamba2-370m.json``.

The reference follows the Mamba-2 description (arXiv:2405.21060): pre-norm
RMSNorm, one input projection to [z, x, B, C, dt], a causal depthwise
convolution with SiLU over [x, B, C], the selective state recurrence
h_t = exp(-exp(A_log) dt_t) h_{t-1} + dt_t B_t x_t^T and y_t = C_t h_t + D x_t
with one B/C group shared by all heads, the gate y * silu(z), RMSNorm over
the inner width, the output projection, and the tied output embedding.  It
runs the recurrence token by token in float32 at full matmul precision,
imports nothing of the program, and reads the program's parameter layout,
made here and checked against the program's own shapes by the harness.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def padded_vocab(c: dict) -> int:
    """Rows of the embedding: the vocabulary padded as the source pads it."""
    m = c["pad_vocab_size_multiple"]
    return -(-c["vocab_size"] // m) * m


def _sizes(c: dict) -> tuple[int, ...]:
    d = c["d_model"]
    d_in = c["expand"] * d
    return (d, c["n_layer"], d_in, d_in // c["headdim"], c["d_state"],
            c["headdim"], c["d_conv"], padded_vocab(c))


def program_arch(c: dict, arch):
    """The program's config set to what `c` states, through its own
    `ArchConfig`: its mamba2-370m pads the vocabulary to 50304 and keeps
    chunk 128 and eps 1e-6, where the source states 50288, 256 and 1e-5."""
    from dataclasses import replace
    return arch.with_(vocab=padded_vocab(c), norm_eps=c["rms_norm_eps"],
                      ssm=replace(arch.ssm, chunk=c["chunk_size"]))


def program_mismatches(c: dict, arch) -> list[str]:
    """Where the program's config of the same name differs from `c`."""
    s = arch.ssm
    have = {"d_model": arch.d_model, "n_layer": arch.n_layers,
            "vocab_size": arch.vocab, "rms_norm_eps": arch.norm_eps,
            "tie_embeddings": arch.tie_embeddings, "family": arch.family,
            "torch_dtype": arch.dtype,
            "d_state": s.d_state if s else None,
            "headdim": s.head_dim if s else None,
            "expand": s.expand if s else None,
            "d_conv": s.d_conv if s else None,
            "chunk_size": s.chunk if s else None}
    want = dict(c, family="ssm", vocab_size=padded_vocab(c))
    return [f"{k}: program {v!r}, config {want[k]!r}"
            for k, v in have.items() if want[k] != v]


def make_params(c: dict, key) -> dict:
    """The served weights (bf16; the decay, skip and step-bias vectors in
    float32, as the program keeps them), in the program's layout."""
    d, nl, d_in, nh, n, hp, kc, v = _sizes(c)
    dt = jnp.dtype(c["torch_dtype"])
    f32 = jnp.float32
    conv_ch = d_in + 2 * n
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, f32) * std

    def scale(shape):
        return (1.0 + normal(shape, 0.1)).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, f32, lo, hi)

    step = jnp.exp(uniform((nl, nh), jnp.log(1e-3), jnp.log(1e-1)))
    layer = {
        "mixer_norm": {"scale": scale((nl, d))},
        "ssm": {
            "in_proj": normal((nl, d, 2 * d_in + 2 * n + nh), d ** -0.5
                              ).astype(dt),
            "conv_w": normal((nl, kc, conv_ch), kc ** -0.5).astype(dt),
            "conv_b": normal((nl, conv_ch), 0.1).astype(dt),
            "A_log": jnp.log(uniform((nl, nh), 1.0, 16.0)),
            "D": 1.0 + normal((nl, nh), 0.1),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),   # softplus^-1
            "norm": {"scale": scale((nl, d_in))},
            "out_proj": normal((nl, d_in, d),
                               d_in ** -0.5 * (2 * nl) ** -0.5).astype(dt),
        },
    }
    table = normal((v, d), 0.02).astype(dt)
    return {"periods": {"slot0": layer}, "final_norm": {"scale": scale((d,))},
            "embed": {"table": table}}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def forward(c: dict, params: dict, tokens, act=None) -> jax.Array:
    """Logits (S, V) in float32 of every position of `tokens` (S,).  `act`,
    where given, rounds the input of every matmul (the fp8 control)."""
    d, nl, d_in, nh, n, hp, kc, v = _sizes(c)
    act = act or (lambda x: x)
    eps = c["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[0]

    def layer(x, lp):
        m = lp["ssm"]
        h = act(_rms(x, lp["mixer_norm"]["scale"], eps))
        proj = h @ m["in_proj"]
        z = proj[:, :d_in]
        xbc = proj[:, d_in:2 * d_in + 2 * n]
        dt_raw = proj[:, 2 * d_in + 2 * n:]
        padded = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1])), xbc])
        conv = sum(padded[i:i + s] * m["conv_w"][i] for i in range(kc))
        xbc = jax.nn.silu(conv + m["conv_b"])
        xs, b, cm = xbc[:, :d_in], xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
        step = jax.nn.softplus(dt_raw + m["dt_bias"])             # (S, H)
        decay = jnp.exp(-jnp.exp(m["A_log"]) * step)               # (S, H)
        xh = xs.reshape(s, nh, hp)

        def token(state, inp):                                     # (H, N, P)
            a_t, b_t, c_t, x_t, dt_t = inp
            state = (a_t[:, None, None] * state
                     + dt_t[:, None, None] * b_t[None, :, None]
                     * x_t[:, None, :])
            return state, jnp.einsum("n,hnp->hp", c_t, state)

        _, y = jax.lax.scan(token, jnp.zeros((nh, n, hp)),
                            (decay, b, cm, xh, step))
        y = (y + m["D"][None, :, None] * xh).reshape(s, d_in)
        y = act(_rms(y * jax.nn.silu(z), m["norm"]["scale"], eps))
        return x + y @ m["out_proj"], None

    with jax.default_matmul_precision("highest"):
        x = p["embed"]["table"][tokens]
        x, _ = jax.lax.scan(layer, x, p["periods"]["slot0"])
        x = act(_rms(x, p["final_norm"]["scale"], eps))
        return x @ p["embed"]["table"].T


# ---------------------------------------------------------------------------
# work of one decode step (batch 1), from the shapes alone
# ---------------------------------------------------------------------------
def param_count(c: dict) -> int:
    d, nl, d_in, nh, n, hp, kc, v = _sizes(c)
    conv_ch = d_in + 2 * n
    per_layer = (d + d * (2 * d_in + 2 * n + nh) + kc * conv_ch + conv_ch
                 + 3 * nh + d_in + d_in * d)
    return nl * per_layer + v * d + d


def param_bytes(c: dict) -> int:
    d, nl, d_in, nh, n, hp, kc, v = _sizes(c)
    return param_count(c) * 2 + nl * 3 * nh * 2        # 3 vectors in f32


def state_bytes(c: dict) -> int:
    """Decode state of one sequence: the SSM state (f32) and the last
    `d_conv - 1` convolution inputs (bf16) of every layer."""
    d, nl, d_in, nh, n, hp, kc, v = _sizes(c)
    return nl * (nh * n * hp * 4 + (kc - 1) * (d_in + 2 * n) * 2)


def decode_bytes(c: dict, cache_len: int) -> int:
    """Least HBM bytes of a step: every parameter once and the state read
    and written, whatever the position."""
    return param_bytes(c) + 2 * state_bytes(c)


def decode_flops(c: dict, cache_len: int) -> int:
    """Matmul and state operations of a step: the projections, the output
    head, the convolution and the recurrence (3 per state element to
    update it, 2 to read it out)."""
    d, nl, d_in, nh, n, hp, kc, v = _sizes(c)
    conv_ch = d_in + 2 * n
    weights = nl * (d * (2 * d_in + 2 * n + nh) + d_in * d) + v * d
    return 2 * weights + nl * (2 * kc * conv_ch + 5 * nh * n * hp)
