"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Layers are organized into repeating *periods* (dense: period 1; jamba:
period 8 with attention at slot 4 and MoE at odd slots), and the model scans
over stacked period parameters with jax.checkpoint applied per the remat
policy — HLO size stays O(period), activation memory O(n_periods · resid).

Three entry points (all pure functions of (params, batch)):

* `loss_fn`     — next-token cross entropy (+ MoE aux, z-loss), for train;
* `prefill_fn`  — forward returning hidden states and decode caches;
* `decode_fn`   — one-token step updating caches (the `serve_step` the
                  decode-shape dry-runs lower).

VLM (internvl2): stub patch embeddings (B, n_patches, frontend_dim) are
projected and prepended; labels are masked over patch positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..parallel import ctx
from .config import ArchConfig
from .layers import (attention, attention_decode, attention_decode_ring,
                     attention_init, embed, embedding_init, mlp, mlp_init,
                     rmsnorm, rmsnorm_init, rmsnorm_spec, unembed, _dtype,
                     _init_dense)
from .mamba2 import mamba2_apply, mamba2_decode, mamba2_init, _dims
from .moe import moe_apply, moe_init

FRONTEND_DIM = {"patch": 1024, "audio": 384}


def frontend_dim(cfg: ArchConfig) -> int:
    """Stub embedding width: ViT hidden for patch frontends; d_model for the
    audio conv stub (whisper's conv output is already d_model)."""
    if cfg.frontend == "audio":
        return cfg.d_model
    return FRONTEND_DIM[cfg.frontend]


# =========================================================================
# structure
# =========================================================================
def period_length(cfg: ArchConfig) -> int:
    p = 1
    if cfg.attn_period:
        p = cfg.attn_period
    if cfg.moe is not None:
        p = int(math.lcm(p, cfg.moe.every))
    return p


def n_periods(cfg: ArchConfig) -> int:
    p = period_length(cfg)
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return cfg.n_layers // p


def slot_kind(cfg: ArchConfig, slot: int) -> tuple[str, str]:
    """(mixer, ffn) for layer-index `slot` within a period."""
    mixer = "attn" if cfg.is_attn_layer(slot) else "ssm"
    if cfg.family == "ssm":
        ffn = "none"                       # mamba2 backbone has no MLP
    elif cfg.is_moe_layer(slot):
        ffn = "moe"
    else:
        ffn = "mlp"
    return mixer, ffn


# =========================================================================
# init
# =========================================================================
def _slot_init(key, cfg: ArchConfig, slot: int) -> tuple[dict, dict]:
    mixer, ffn = slot_kind(cfg, slot)
    kb, km, kf = jax.random.split(key, 3)
    dt = _dtype(cfg)
    p: dict = {"mixer_norm": rmsnorm_init(cfg.d_model, dt)}
    s: dict = {"mixer_norm": rmsnorm_spec()}
    if mixer == "attn":
        p["attn"], s["attn"] = attention_init(km, cfg)
    else:
        p["ssm"], s["ssm"] = mamba2_init(km, cfg)
    if ffn != "none":
        p["ffn_norm"] = rmsnorm_init(cfg.d_model, dt)
        s["ffn_norm"] = rmsnorm_spec()
        if ffn == "moe":
            p["moe"], s["moe"] = moe_init(kf, cfg)
        else:
            p["mlp"], s["mlp"] = mlp_init(kf, cfg)
    return p, s


def init_params(key, cfg: ArchConfig) -> tuple[dict, dict]:
    """Returns (params, logical sharding spec pytree of the same shape)."""
    nper = n_periods(cfg)
    plen = period_length(cfg)
    keys = jax.random.split(key, nper * plen + 3)
    period_trees = []
    spec_slots = {}
    for per in range(nper):
        slots = {}
        for slot in range(plen):
            sp, ss = _slot_init(keys[per * plen + slot], cfg, slot)
            slots[f"slot{slot}"] = sp
            if per == 0:
                spec_slots[f"slot{slot}"] = ss
        period_trees.append(slots)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *period_trees) \
        if nper > 1 else jax.tree.map(lambda x: x[None], period_trees[0])
    spec_stacked = jax.tree.map(
        lambda spec: ("layers",) + tuple(spec),
        spec_slots, is_leaf=lambda x: isinstance(x, tuple))

    p = {"periods": stacked,
         "final_norm": rmsnorm_init(cfg.d_model, _dtype(cfg))}
    s = {"periods": spec_stacked, "final_norm": rmsnorm_spec()}
    p["embed"], s["embed"] = embedding_init(keys[-1], cfg)
    if not cfg.tie_embeddings:
        p["head"], s["head"] = embedding_init(keys[-2], cfg)
    if cfg.frontend is not None:
        fd = FRONTEND_DIM[cfg.frontend]
        p["frontend_proj"] = _init_dense(keys[-3], fd, cfg.d_model,
                                         _dtype(cfg))
        s["frontend_proj"] = (None, "embed")
    return p, s


# =========================================================================
# forward
# =========================================================================
def _residual(cfg: ArchConfig, y: jax.Array) -> jax.Array:
    """A sublayer's output as it joins the residual stream."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return y


def _apply_slot(sp: dict, x: jax.Array, cfg: ArchConfig, slot: int,
                positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    mixer, ffn = slot_kind(cfg, slot)
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(sp["mixer_norm"], x, cfg.norm_eps)
    if mixer == "attn":
        y = attention(sp["attn"], h, cfg, positions)
    else:
        y = mamba2_apply(sp["ssm"], h, cfg)
    x = x + _residual(cfg, y).astype(x.dtype)
    # pin the residual's batch sharding at every slot: in heterogeneous
    # periods (jamba) GSPMD otherwise replicates the stream mid-period and
    # the MoE scatters blow up to global-batch all-reduces (§Perf#9)
    x = ctx.shard_batch(x)
    if ffn != "none":
        h = rmsnorm(sp["ffn_norm"], x, cfg.norm_eps)
        if ffn == "moe":
            y, aux = moe_apply(sp["moe"], h, cfg)
        else:
            y = mlp(sp["mlp"], h)
        x = x + _residual(cfg, y).astype(x.dtype)
        x = ctx.shard_batch(x)
    return x, aux


def _remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def backbone(params: dict, x: jax.Array, cfg: ArchConfig,
             positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (hidden (B, S, D), aux loss)."""
    plen = period_length(cfg)

    def period_body(carry, pp):
        x, aux = carry
        for slot in range(plen):
            x, a = _apply_slot(pp[f"slot{slot}"], x, cfg, slot, positions)
            aux = aux + a
        # sequence-parallel residual (Megatron-SP): the scan saves the
        # inter-layer residual stack for backward — sharding its sequence
        # dim over the model axis cuts that stack 16x (§Perf#5: 60 GiB/dev
        # -> <4 GiB/dev for qwen2.5-14b train_4k)
        x = ctx.shard_spec(x, "batch", "model", None)
        return (x, aux), None

    body = _remat(cfg, period_body)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["periods"])
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(n_periods(cfg)):
            pp = jax.tree.map(lambda a: a[i], params["periods"])
            (x, aux), _ = body((x, aux), pp)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def embed_tokens(params: dict, cfg: ArchConfig, tokens: jax.Array
                 ) -> jax.Array:
    x = embed(params["embed"], tokens)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def embed_inputs(params: dict, cfg: ArchConfig, tokens: jax.Array,
                 frontend: jax.Array | None) -> jax.Array:
    x = embed_tokens(params, cfg, tokens)
    if cfg.frontend is not None and frontend is not None:
        fe = frontend.astype(x.dtype) @ params["frontend_proj"]
        x = jnp.concatenate([fe, x], axis=1)
    return ctx.shard_batch(x)


def _scale_logits(cfg: ArchConfig, logits: jax.Array) -> jax.Array:
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def logits_fn(params: dict, cfg: ArchConfig, hidden: jax.Array) -> jax.Array:
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    return _scale_logits(cfg, unembed(table, hidden))


def _ce_terms(cfg: ArchConfig, table: jax.Array, hidden: jax.Array,
              labels: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Cross-entropy pieces for one sequence chunk (f32 logits live only
    within the chunk)."""
    logits = _scale_logits(cfg, jnp.einsum(
        "bsd,vd->bsv", hidden.astype(jnp.float32), table.astype(jnp.float32)))
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return (jnp.sum((logz - gold) * mask), jnp.sum((logz ** 2) * mask),
            mask.sum())


def loss_fn(params: dict, cfg: ArchConfig, batch: dict,
            loss_chunk: int = 512) -> jax.Array:
    """batch: tokens (B, S_text) int32, labels (B, S_text) int32 (-1 =
    ignore), optional frontend (B, F, fd).

    The cross entropy is computed over SEQUENCE CHUNKS with rematerialized
    bodies, so the (B, S, V) f32 logits never exist at once — only
    (B, chunk, V) does (§Perf#6)."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    fe = batch.get("frontend")
    x = embed_inputs(params, cfg, tokens, fe)
    positions = jnp.arange(x.shape[1])
    hidden, aux = backbone(params, x, cfg, positions)
    if fe is not None:   # loss only over text positions
        hidden = hidden[:, fe.shape[1]:]
    table = params["embed"]["table"] if cfg.tie_embeddings \
        else params["head"]["table"]
    s = hidden.shape[1]
    if s % loss_chunk or s <= loss_chunk:
        nll, z2, n = _ce_terms(cfg, table, hidden, labels)
    else:
        nc = s // loss_chunk
        hc = hidden.reshape(hidden.shape[0], nc, loss_chunk, -1)
        lc = labels.reshape(labels.shape[0], nc, loss_chunk)

        def chunk_body(carry, inp):
            h, l = inp
            t_nll, t_z2, t_n = _ce_terms(cfg, table, h, l)
            return (carry[0] + t_nll, carry[1] + t_z2, carry[2] + t_n), None

        (nll, z2, n), _ = jax.lax.scan(
            jax.checkpoint(chunk_body),
            (jnp.zeros((), jnp.float32),) * 3,
            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)))
    denom = jnp.maximum(n, 1.0)
    return nll / denom + 1e-4 * z2 / denom + aux


# =========================================================================
# serving: caches, prefill, decode
# =========================================================================
def cache_spec(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    """ShapeDtypeStructs for the decode caches (also used by input_specs)."""
    plen = period_length(cfg)
    nper = n_periods(cfg)
    spec: dict = {}
    for slot in range(plen):
        mixer, _ = slot_kind(cfg, slot)
        if mixer == "attn":
            hd = cfg.resolved_head_dim
            kv_len = min(max_len, cfg.window) if cfg.window else max_len
            shp = (nper, batch, cfg.n_kv_heads, kv_len, hd)
            spec[f"slot{slot}"] = {
                "k": jax.ShapeDtypeStruct(shp, jnp.bfloat16),
                "v": jax.ShapeDtypeStruct(shp, jnp.bfloat16),
            }
        else:
            d_in, nh, n, p_dim = _dims(cfg)
            conv_ch = d_in + 2 * n
            spec[f"slot{slot}"] = {
                "conv": jax.ShapeDtypeStruct(
                    (nper, batch, cfg.ssm.d_conv - 1, conv_ch),
                    jnp.bfloat16),
                "ssm": jax.ShapeDtypeStruct((nper, batch, nh, n, p_dim),
                                            jnp.float32),
            }
    return spec


def cache_pos_axes(cfg: ArchConfig) -> dict:
    """Per `cache_spec` leaf, the axis that holds one entry per token, or
    None for recurrent state.  `decode_fn` writes position ``cache_len``
    there, so until a ring buffer wraps the entries at or past
    ``cache_len`` are still `init_cache`'s zeros."""
    out: dict = {}
    for slot in range(period_length(cfg)):
        mixer, _ = slot_kind(cfg, slot)
        out[f"slot{slot}"] = ({"k": 3, "v": 3} if mixer == "attn"
                              else {"conv": None, "ssm": None})
    return out


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                        cache_spec(cfg, batch, max_len))


def prefill_fn(params: dict, cfg: ArchConfig, batch: dict) -> jax.Array:
    """Forward over the prompt; returns last-position logits.  (Cache
    construction during prefill reuses the same backbone; the dry-run
    prefill cell lowers exactly this compute.)"""
    tokens = batch["tokens"]
    fe = batch.get("frontend")
    x = embed_inputs(params, cfg, tokens, fe)
    positions = jnp.arange(x.shape[1])
    hidden, _ = backbone(params, x, cfg, positions)
    return logits_fn(params, cfg, hidden[:, -1:])


def decode_fn(params: dict, cfg: ArchConfig, token: jax.Array, cache: dict,
              cache_len: jax.Array) -> tuple[jax.Array, dict]:
    """One decode step: token (B, 1) -> (logits (B, 1, V), new cache).
    The three kinds of sublayer run under the scopes ``attn``, ``ssm`` and
    ``moe``, which name their ops in a device trace."""
    x = embed_tokens(params, cfg, token)
    plen = period_length(cfg)

    def period_body(x, inp):
        pp, cc = inp
        new_cc = {}
        for slot in range(plen):
            sp = pp[f"slot{slot}"]
            cs = cc[f"slot{slot}"]
            mixer, ffn = slot_kind(cfg, slot)
            h = rmsnorm(sp["mixer_norm"], x, cfg.norm_eps)
            if mixer == "attn":
                # ring buffer iff the cache was allocated at window size
                use_ring = (cfg.window is not None
                            and cs["k"].shape[2] == cfg.window)
                dec = attention_decode_ring if use_ring else attention_decode
                with jax.named_scope("attn"):
                    o, nk, nv = dec(sp["attn"], h, cfg, cs["k"], cs["v"],
                                    cache_len)
                x = x + _residual(cfg, o)
                new_cc[f"slot{slot}"] = {"k": nk, "v": nv}
            else:
                with jax.named_scope("ssm"):
                    o, nconv, nssm = mamba2_decode(sp["ssm"], h, cfg,
                                                   cs["conv"], cs["ssm"])
                x = x + _residual(cfg, o)
                new_cc[f"slot{slot}"] = {"conv": nconv, "ssm": nssm}
            if ffn != "none":
                h = rmsnorm(sp["ffn_norm"], x, cfg.norm_eps)
                if ffn == "moe":
                    with jax.named_scope("moe"):
                        y, _ = moe_apply(sp["moe"], h, cfg, dropless=True)
                else:
                    y = mlp(sp["mlp"], h)
                x = x + _residual(cfg, y).astype(x.dtype)
        return x, new_cc

    x, new_cache = jax.lax.scan(period_body, x, (params["periods"], cache))
    hidden = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_fn(params, cfg, hidden), new_cache
