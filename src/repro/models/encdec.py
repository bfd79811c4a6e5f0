"""Encoder-decoder backbone (whisper-tiny family).

The conv audio frontend is a STUB per the brief: `input_specs()` provides
precomputed frame embeddings (B, enc_seq, d_model) — the output of whisper's
two conv layers.  The transformer backbone is real: a bidirectional encoder
with learned positions, and a causal decoder with learned positions and
cross-attention.  Learned positional tables are sized per shape
(max(448, seq)) as recorded in DESIGN.md §Arch-applicability.

Approximations vs the HF checkpoint (documented): RMSNorm instead of
LayerNorm, SwiGLU-style MLP replaced by a 2-matrix GELU MLP (matching
whisper's), RoPE not used (learned positions, as in whisper).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from .config import ArchConfig
from .layers import (attention_init, attention_out, attention_qkv, embed,
                     embedding_init, rmsnorm, rmsnorm_init, rmsnorm_spec,
                     _dtype, _init_dense)


def _gelu_mlp_init(key, cfg: ArchConfig) -> tuple[dict, dict]:
    d, ff = cfg.d_model, cfg.d_ff
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {"w_in": _init_dense(k1, d, ff, dt),
         "w_out": _init_dense(k2, ff, d, dt,
                              scale=ff ** -0.5 / (2 * cfg.n_layers) ** 0.5)}
    return p, {"w_in": ("embed", "ff"), "w_out": ("ff", "embed")}


def _gelu_mlp(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.gelu((x @ p["w_in"]).astype(jnp.float32))
    return h.astype(x.dtype) @ p["w_out"]


def _attn_nopos(p: dict, x: jax.Array, cfg: ArchConfig, *, causal: bool,
                kv: jax.Array | None = None) -> jax.Array:
    """Attention without RoPE (learned positions added at embedding time).
    kv != None switches to cross-attention against encoder states."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    src = kv if kv is not None else x
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = (src @ p["wk"]).reshape(b, src.shape[1], cfg.n_kv_heads,
                                hd).transpose(0, 2, 1, 3)
    v = (src @ p["wv"]).reshape(b, src.shape[1], cfg.n_kv_heads,
                                hd).transpose(0, 2, 1, 3)
    o = kops.flash_attention(q, k, v, causal=causal, impl=cfg.attn_impl)
    return attention_out(p, o)


def _dec_layer_init(key, cfg: ArchConfig) -> tuple[dict, dict]:
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    p, s = {}, {}
    p["self_norm"] = rmsnorm_init(cfg.d_model, dt)
    s["self_norm"] = rmsnorm_spec()
    p["self_attn"], s["self_attn"] = attention_init(ks[0], cfg)
    p["cross_norm"] = rmsnorm_init(cfg.d_model, dt)
    s["cross_norm"] = rmsnorm_spec()
    p["cross_attn"], s["cross_attn"] = attention_init(ks[1], cfg)
    p["ffn_norm"] = rmsnorm_init(cfg.d_model, dt)
    s["ffn_norm"] = rmsnorm_spec()
    p["mlp"], s["mlp"] = _gelu_mlp_init(ks[2], cfg)
    return p, s


def init_params(key, cfg: ArchConfig, max_seq: int) -> tuple[dict, dict]:
    dt = _dtype(cfg)
    n_pos = max(cfg.max_decoder_positions or 448, max_seq)
    ks = jax.random.split(key, cfg.enc_layers + cfg.n_layers + 4)
    enc_layers, enc_spec0 = [], None
    for i in range(cfg.enc_layers):
        ksl = jax.random.split(ks[i], 2)
        p = {"attn_norm": rmsnorm_init(cfg.d_model, dt),
             "ffn_norm": rmsnorm_init(cfg.d_model, dt)}
        s = {"attn_norm": rmsnorm_spec(), "ffn_norm": rmsnorm_spec()}
        p["attn"], s["attn"] = attention_init(ksl[0], cfg)
        p["mlp"], s["mlp"] = _gelu_mlp_init(ksl[1], cfg)
        enc_layers.append(p)
        enc_spec0 = enc_spec0 or s
    dec_layers, dec_spec0 = [], None
    for i in range(cfg.n_layers):
        p, s = _dec_layer_init(ks[cfg.enc_layers + i], cfg)
        dec_layers.append(p)
        dec_spec0 = dec_spec0 or s

    def stack(trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees) \
            if len(trees) > 1 else jax.tree.map(lambda x: x[None], trees[0])

    def stack_spec(s):
        return jax.tree.map(lambda sp: ("layers",) + tuple(sp), s,
                            is_leaf=lambda x: isinstance(x, tuple))

    p = {
        "enc_layers": stack(enc_layers),
        "dec_layers": stack(dec_layers),
        "enc_pos": (jax.random.normal(ks[-1], (cfg.enc_seq, cfg.d_model),
                                      jnp.float32) * 0.02).astype(dt),
        "dec_pos": (jax.random.normal(ks[-2], (n_pos, cfg.d_model),
                                      jnp.float32) * 0.02).astype(dt),
        "enc_norm": rmsnorm_init(cfg.d_model, dt),
        "dec_norm": rmsnorm_init(cfg.d_model, dt),
    }
    s = {
        "enc_layers": stack_spec(enc_spec0),
        "dec_layers": stack_spec(dec_spec0),
        "enc_pos": (None, "embed"),
        "dec_pos": (None, "embed"),
        "enc_norm": rmsnorm_spec(),
        "dec_norm": rmsnorm_spec(),
    }
    p["embed"], s["embed"] = embedding_init(ks[-3], cfg)  # tied head (whisper)
    return p, s


# =========================================================================
# forward
# =========================================================================
def encode(params: dict, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, enc_seq, d_model) stub conv output."""
    x = frames.astype(_dtype(cfg)) + params["enc_pos"][None]

    def body(x, lp):
        h = rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
        x = x + _attn_nopos(lp["attn"], h, cfg, causal=False)
        h = rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
        return x + _gelu_mlp(lp["mlp"], h), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["enc_layers"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def decode_train(params: dict, cfg: ArchConfig, tokens: jax.Array,
                 enc_out: jax.Array) -> jax.Array:
    s = tokens.shape[1]
    x = embed(params["embed"], tokens) + params["dec_pos"][None, :s]

    def body(x, lp):
        h = rmsnorm(lp["self_norm"], x, cfg.norm_eps)
        x = x + _attn_nopos(lp["self_attn"], h, cfg, causal=True)
        h = rmsnorm(lp["cross_norm"], x, cfg.norm_eps)
        x = x + _attn_nopos(lp["cross_attn"], h, cfg, causal=False,
                            kv=enc_out)
        h = rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
        return x + _gelu_mlp(lp["mlp"], h), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["dec_layers"])
    return rmsnorm(params["dec_norm"], x, cfg.norm_eps)


def _ce_terms(table: jax.Array, hidden: jax.Array, labels: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """Masked cross-entropy pieces for one sequence chunk (f32 logits live
    only within the chunk)."""
    logits = jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                        table.astype(jnp.float32))
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask), mask.sum()


def loss_fn(params: dict, cfg: ArchConfig, batch: dict,
            loss_chunk: int = 512) -> jax.Array:
    enc_out = encode(params, cfg, batch["frontend"])
    hidden = decode_train(params, cfg, batch["tokens"], enc_out)
    table = params["embed"]["table"]
    labels = batch["labels"]
    s = hidden.shape[1]
    # cross entropy over SEQUENCE CHUNKS with rematerialized bodies, as in
    # lm.loss_fn (§Perf#6): (B, S, V) f32 logits never exist at once
    if s % loss_chunk or s <= loss_chunk:
        nll, n = _ce_terms(table, hidden, labels)
    else:
        nc = s // loss_chunk
        hc = hidden.reshape(hidden.shape[0], nc, loss_chunk, -1)
        lc = labels.reshape(labels.shape[0], nc, loss_chunk)

        def chunk_body(carry, inp):
            h, l = inp
            t_nll, t_n = _ce_terms(table, h, l)
            return (carry[0] + t_nll, carry[1] + t_n), None

        (nll, n), _ = jax.lax.scan(
            jax.checkpoint(chunk_body),
            (jnp.zeros((), jnp.float32),) * 2,
            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)))
    return nll / jnp.maximum(n, 1.0)


def prefill_fn(params: dict, cfg: ArchConfig, batch: dict) -> jax.Array:
    enc_out = encode(params, cfg, batch["frontend"])
    hidden = decode_train(params, cfg, batch["tokens"], enc_out)
    return jnp.einsum("bsd,vd->bsv", hidden[:, -1:].astype(jnp.float32),
                      params["embed"]["table"].astype(jnp.float32))


# =========================================================================
# serving
# =========================================================================
def cache_spec(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    hd = cfg.resolved_head_dim
    L = cfg.n_layers
    return {
        "k": jax.ShapeDtypeStruct((L, batch, cfg.n_kv_heads, max_len, hd),
                                  jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((L, batch, cfg.n_kv_heads, max_len, hd),
                                  jnp.bfloat16),
        # cross-attention K/V precomputed from the encoder at prefill
        "cross_k": jax.ShapeDtypeStruct(
            (L, batch, cfg.n_kv_heads, cfg.enc_seq, hd), jnp.bfloat16),
        "cross_v": jax.ShapeDtypeStruct(
            (L, batch, cfg.n_kv_heads, cfg.enc_seq, hd), jnp.bfloat16),
    }


def cache_pos_axes(cfg: ArchConfig) -> dict:
    """Per `cache_spec` leaf, the axis that holds one entry per decoded
    token; the cross-attention K/V span the encoder's frames instead."""
    return {"k": 3, "v": 3, "cross_k": None, "cross_v": None}


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                        cache_spec(cfg, batch, max_len))


def decode_fn(params: dict, cfg: ArchConfig, token: jax.Array, cache: dict,
              cache_len: jax.Array) -> tuple[jax.Array, dict]:
    b = token.shape[0]
    hd = cfg.resolved_head_dim
    pos_emb = jax.lax.dynamic_slice_in_dim(params["dec_pos"], cache_len, 1,
                                           axis=0)
    x = embed(params["embed"], token) + pos_emb[None, 0:1]

    def body(x, inp):
        lp, ck, cv, xk, xv = inp
        h = rmsnorm(lp["self_norm"], x, cfg.norm_eps)
        q = (h @ lp["self_attn"]["wq"]).reshape(b, 1, cfg.n_heads, hd
                                                ).transpose(0, 2, 1, 3)
        k = (h @ lp["self_attn"]["wk"]).reshape(b, 1, cfg.n_kv_heads, hd
                                                ).transpose(0, 2, 1, 3)
        v = (h @ lp["self_attn"]["wv"]).reshape(b, 1, cfg.n_kv_heads, hd
                                                ).transpose(0, 2, 1, 3)
        nk = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                 cache_len, axis=2)
        nv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                 cache_len, axis=2)
        mask = jnp.arange(nk.shape[2])[None, None, None, :] <= cache_len
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       nk.astype(jnp.float32)) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p,
                       nv.astype(jnp.float32)).astype(x.dtype)
        x = x + attention_out(lp["self_attn"], o)
        # cross attention against the cached encoder K/V
        h = rmsnorm(lp["cross_norm"], x, cfg.norm_eps)
        q = (h @ lp["cross_attn"]["wq"]).reshape(b, 1, cfg.n_heads, hd
                                                 ).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       xk.astype(jnp.float32)) * hd ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p,
                       xv.astype(jnp.float32)).astype(x.dtype)
        x = x + attention_out(lp["cross_attn"], o)
        h = rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
        x = x + _gelu_mlp(lp["mlp"], h)
        return x, (nk, nv)

    x, (nks, nvs) = jax.lax.scan(
        body, x, (params["dec_layers"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    hidden = rmsnorm(params["dec_norm"], x, cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                        params["embed"]["table"].astype(jnp.float32))
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = nks, nvs
    return logits, new_cache
