"""qwen3-0.6b: weights made from a seed, the plain reference forward pass,
and the work of one decode step, all from the sizes in ``qwen3-0.6b.json``.

The reference follows the published Qwen3 description (pre-norm RMSNorm,
RMSNorm on each query and key head, rotary embedding by halves, grouped
query attention, SwiGLU, tied input and output embedding) in float32 at
full matmul precision.  It imports nothing of the program; the parameter
layout it reads is the program's, made here and checked against the
program's own shapes by the harness.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _sizes(c: dict) -> tuple[int, ...]:
    return (c["hidden_size"], c["num_hidden_layers"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["vocab_size"])


def program_arch(c: dict, arch):
    """The program's config as `c` states it: its qwen3-0.6b already is."""
    return arch


def program_mismatches(c: dict, arch) -> list[str]:
    """Where the program's config of the same name differs from `c`."""
    want = {"d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab": c["vocab_size"], "norm_eps": c["rms_norm_eps"],
            "rope_theta": c["rope_theta"],
            "tie_embeddings": c["tie_word_embeddings"], "qk_norm": True,
            "family": "dense", "dtype": c["torch_dtype"]}
    return [f"{k}: program {getattr(arch, k)!r}, config {v!r}"
            for k, v in want.items() if getattr(arch, k) != v]


def make_params(c: dict, key) -> dict:
    """The served weights (bf16), in the program's layout."""
    d, nl, nh, nkv, hd, ff, v = _sizes(c)
    dt = jnp.dtype(c["torch_dtype"])
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, extra=1.0):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * (fan_in ** -0.5) * extra).astype(dt)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    depth = (2 * nl) ** -0.5
    layer = {
        "mixer_norm": {"scale": scale((nl, d))},
        "attn": {"wq": dense((nl, d, nh * hd), d),
                 "wk": dense((nl, d, nkv * hd), d),
                 "wv": dense((nl, d, nkv * hd), d),
                 "wo": dense((nl, nh * hd, d), nh * hd, depth),
                 "q_norm": scale((nl, hd)), "k_norm": scale((nl, hd))},
        "ffn_norm": {"scale": scale((nl, d))},
        "mlp": {"w_gate": dense((nl, d, ff), d), "w_up": dense((nl, d, ff), d),
                "w_down": dense((nl, ff, d), ff, depth)},
    }
    table = (jax.random.normal(next(ks), (v, d), jnp.float32) * 0.02
             ).astype(dt)
    return {"periods": {"slot0": layer}, "final_norm": {"scale": scale((d,))},
            "embed": {"table": table}}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def forward(c: dict, params: dict, tokens, act=None) -> jax.Array:
    """Logits (S, V) in float32 of every position of `tokens` (S,).  `act`,
    where given, rounds the input of every matmul (the fp8 control)."""
    d, nl, nh, nkv, hd, ff, v = _sizes(c)
    act = act or (lambda x: x)
    eps = c["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[0]
    half = hd // 2
    inv = c["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rotary(x):                                     # (S, H, hd)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        h = act(_rms(x, lp["mixer_norm"]["scale"], eps))
        q = _rms((h @ a["wq"]).reshape(s, nh, hd), a["q_norm"], eps)
        k = _rms((h @ a["wk"]).reshape(s, nkv, hd), a["k_norm"], eps)
        val = (h @ a["wv"]).reshape(s, nkv, hd)
        q, k = rotary(q), rotary(k)
        k = jnp.repeat(k, nh // nkv, axis=1)           # query head i reads
        val = jnp.repeat(val, nh // nkv, axis=1)       # key head i // group
        sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), val)
        x = x + act(o.reshape(s, nh * hd)) @ a["wo"]
        h = act(_rms(x, lp["ffn_norm"]["scale"], eps))
        x = x + act(jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) \
            @ m["w_down"]
        return x, None

    with jax.default_matmul_precision("highest"):
        x = p["embed"]["table"][tokens]
        x, _ = jax.lax.scan(layer, x, p["periods"]["slot0"])
        x = act(_rms(x, p["final_norm"]["scale"], eps))
        return x @ p["embed"]["table"].T


# ---------------------------------------------------------------------------
# work of one decode step (batch 1), from the shapes alone
# ---------------------------------------------------------------------------
def param_count(c: dict) -> int:
    d, nl, nh, nkv, hd, ff, v = _sizes(c)
    per_layer = (d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 2 * hd
                 + 3 * d * ff + 2 * d)
    return nl * per_layer + v * d + d


def kv_bytes_per_position(c: dict) -> int:
    d, nl, nh, nkv, hd, ff, v = _sizes(c)
    return nl * 2 * nkv * hd * 2                        # K and V, bf16


def decode_bytes(c: dict, cache_len: int) -> int:
    """Least HBM bytes of the step at position `cache_len`: every parameter
    once, the K/V of the `cache_len` positions before it read, its own
    written."""
    return param_count(c) * 2 + kv_bytes_per_position(c) * (cache_len + 1)


def decode_flops(c: dict, cache_len: int) -> int:
    """Matmul operations of the step: weights (the output head included)
    and attention over `cache_len + 1` positions."""
    d, nl, nh, nkv, hd, ff, v = _sizes(c)
    weights = nl * (d * nh * hd + 2 * d * nkv * hd + nh * hd * d
                    + 3 * d * ff) + v * d
    attn = nl * 2 * nh * hd * (cache_len + 1)
    return 2 * (weights + attn)

