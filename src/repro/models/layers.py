"""Core layers: norms, RoPE, GQA attention (qk-norm / bias / SWA), SwiGLU.

Pure-JAX parameter-dict modules: each layer is (init(key, cfg) -> params,
apply(params, x, ...) -> y).  Logical sharding axes for every parameter are
produced alongside init as a matching pytree of tuples (see
repro.parallel.sharding for the logical->mesh resolution).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from .config import ArchConfig


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _init_dense(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


# =========================================================================
# norms
# =========================================================================
def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_spec() -> dict:
    return {"scale": ("embed",)}


def rmsnorm(params: dict, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# =========================================================================
# rotary position embedding
# =========================================================================
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, S, D), positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
        ang = ang[None, None]                      # (1, 1, S, half)
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs
        ang = ang[:, None]                         # (B, 1, S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# =========================================================================
# GQA attention
# =========================================================================
def attention_init(key, cfg: ArchConfig) -> tuple[dict, dict]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init_dense(ks[0], d, nh * hd, dt),
        "wk": _init_dense(ks[1], d, nkv * hd, dt),
        "wv": _init_dense(ks[2], d, nkv * hd, dt),
        "wo": _init_dense(ks[3], nh * hd, d, dt,
                          scale=(nh * hd) ** -0.5 / (2 * cfg.n_layers) ** 0.5),
    }
    s = {
        "wq": ("embed", "q_proj"),
        "wk": ("embed", "kv_proj"),
        "wv": ("embed", "kv_proj"),
        "wo": ("q_proj", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
        s["bq"], s["bk"], s["bv"] = ("q_proj",), ("kv_proj",), ("kv_proj",)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
        s["q_norm"], s["k_norm"] = (None,), (None,)
    return p, s


def _head_rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def attention_qkv(params: dict, x: jax.Array, cfg: ArchConfig,
                  positions: jax.Array) -> tuple[jax.Array, jax.Array,
                                                 jax.Array]:
    """x (B, S, D) -> q (B, H, S, hd), k/v (B, Hkv, S, hd), roped unless
    the config has no position embedding."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"])
    k = (x @ params["wk"])
    v = (x @ params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = _head_rms(q, params["q_norm"], cfg.norm_eps)
        k = _head_rms(k, params["k_norm"], cfg.norm_eps)
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def score_scale(cfg: ArchConfig) -> float:
    return cfg.attn_scale or cfg.resolved_head_dim ** -0.5


def attention_out(params: dict, o: jax.Array) -> jax.Array:
    """o (B, H, S, hd) -> (B, S, D)."""
    b, h, s, hd = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, h * hd) @ params["wo"]


def attention(params: dict, x: jax.Array, cfg: ArchConfig,
              positions: jax.Array, *, causal: bool = True) -> jax.Array:
    q, k, v = attention_qkv(params, x, cfg, positions)
    o = kops.flash_attention(q, k, v, causal=causal, window=cfg.window,
                             scale=cfg.attn_scale, impl=cfg.attn_impl)
    return attention_out(params, o)


def attention_decode(params: dict, x: jax.Array, cfg: ArchConfig,
                     cache_k: jax.Array, cache_v: jax.Array,
                     cache_len: jax.Array) -> tuple[jax.Array, jax.Array,
                                                    jax.Array]:
    """One-token decode: x (B, 1, D); cache_k/v (B, Hkv, S, hd) ring-ish
    buffers filled up to cache_len.  Returns (out, new_k, new_v)."""
    b = x.shape[0]
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q, k, v = attention_qkv(params, x, cfg, pos)
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype),
                                             cache_len, axis=2)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype),
                                             cache_len, axis=2)
    skv = ck.shape[2]
    # grouped GQA: never materialize the repeated (or fp32) cache — the
    # einsum reads bf16 K/V once and accumulates in f32 (perf log §Perf#1:
    # the repeat+astype version all-gathered 2×36 GiB per decode step)
    g = cfg.n_heads // cfg.n_kv_heads
    b, _, sq, hd = q.shape
    qg = q.reshape(b, cfg.n_kv_heads, g * sq, hd)
    s = jnp.einsum("bhqd,bhkd->bhqk", qg, ck,
                   preferred_element_type=jnp.float32) * score_scale(cfg)
    idx = jnp.arange(skv)
    mask = idx[None, None, None, :] <= cache_len
    if cfg.window is not None:
        mask &= idx[None, None, None, :] > cache_len - cfg.window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(ck.dtype), cv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(b, cfg.n_heads, sq, hd).astype(x.dtype)
    return attention_out(params, o), ck, cv


def attention_decode_ring(params: dict, x: jax.Array, cfg: ArchConfig,
                          cache_k: jax.Array, cache_v: jax.Array,
                          cache_len: jax.Array) -> tuple[jax.Array, jax.Array,
                                                         jax.Array]:
    """Sliding-window decode with a ring-buffer cache of width W=window:
    slot i holds absolute position  cache_len - ((cache_len - i) mod W),
    so the cache is O(W) regardless of sequence length (the sub-quadratic
    long-context path for SWA architectures)."""
    w = cache_k.shape[2]
    b = x.shape[0]
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q, k, v = attention_qkv(params, x, cfg, pos)
    slot = cache_len % w
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype),
                                             slot, axis=2)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype),
                                             slot, axis=2)
    idx = jnp.arange(w)
    abs_pos = cache_len - jnp.mod(cache_len - idx, w)
    mask = abs_pos >= 0
    g = cfg.n_heads // cfg.n_kv_heads
    bsz, _, sq, hd = q.shape
    qg = q.reshape(bsz, cfg.n_kv_heads, g * sq, hd)
    s = jnp.einsum("bhqd,bhkd->bhqk", qg, ck,
                   preferred_element_type=jnp.float32) * score_scale(cfg)
    s = jnp.where(mask[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(ck.dtype), cv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(bsz, cfg.n_heads, sq, hd).astype(x.dtype)
    return attention_out(params, o), ck, cv


# =========================================================================
# SwiGLU MLP
# =========================================================================
def mlp_init(key, cfg: ArchConfig, d_ff: int | None = None
             ) -> tuple[dict, dict]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    p = {
        "w_gate": _init_dense(ks[0], d, ff, dt),
        "w_up": _init_dense(ks[1], d, ff, dt),
        "w_down": _init_dense(ks[2], ff, d, dt,
                              scale=ff ** -0.5 / (2 * cfg.n_layers) ** 0.5),
    }
    s = {"w_gate": ("embed", "ff"), "w_up": ("embed", "ff"),
         "w_down": ("ff", "embed")}
    return p, s


def mlp(params: dict, x: jax.Array) -> jax.Array:
    g = jax.nn.silu((x @ params["w_gate"]).astype(jnp.float32))
    u = (x @ params["w_up"]).astype(jnp.float32)
    return ((g * u).astype(x.dtype)) @ params["w_down"]


# =========================================================================
# embedding / head
# =========================================================================
def embedding_init(key, cfg: ArchConfig) -> tuple[dict, dict]:
    dt = _dtype(cfg)
    p = {"table": (jax.random.normal(key, (cfg.vocab, cfg.d_model),
                                     jnp.float32) * 0.02).astype(dt)}
    return p, {"table": ("vocab", "embed")}


def embed(params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params: dict, x: jax.Array) -> jax.Array:
    """x (B, S, D) -> logits (B, S, V) in float32."""
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                      params["table"].astype(jnp.float32))
