"""Mixture-of-Experts layer: shared + routed top-k, capacity dispatch.

TPU-adapted GShard/Switch-style dispatch using a sort-based permutation
(no (T, E, C) one-hot tensor):

  1. router -> top-k expert ids + combine weights per token;
  2. token copies sorted by expert id; position-within-expert computed from
     group starts; copies beyond the expert capacity C are dropped;
  3. scatter into an (E, C, D) buffer; batched expert SwiGLU via einsum
     (one (E, D, F) matmul — MXU-friendly);
  4. gather back and combine with gate weights.

Expert weights shard on the ff dimension (tensor-parallel within experts) by
default — every assigned MoE arch has d_ff_expert divisible by 16 — with an
"expert" partition alternative (EP over the model axis) selectable for the
perf study.  Aux losses: load-balance (Switch) + router z-loss.

One chip's share under expert parallelism (`MoEConfig.expert_ranks`): the
layer holds only its rank's `n_held` experts, while the router keeps all
`n_experts` outputs and its top-k over them.  A choice of an expert held
elsewhere goes to the drop slot, so the layer returns its own experts'
part of the result (plus the shared expert, which every rank computes);
the parts of all ranks add up to the whole layer.  On one chip the layer
runs without the exchange that would bring the other ranks' parts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel import ctx
from .config import ArchConfig, MoEConfig
from .layers import _dtype, _init_dense, mlp, mlp_init


def moe_init(key, cfg: ArchConfig) -> tuple[dict, dict]:
    m = cfg.moe
    assert m is not None
    d, fe, eh = cfg.d_model, m.d_ff_expert, m.n_held
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    scale_down = fe ** -0.5 / (2 * cfg.n_layers) ** 0.5
    p = {
        "router": _init_dense(ks[0], d, m.n_experts, jnp.float32, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (eh, d, fe), jnp.float32)
                   * d ** -0.5).astype(dt),
        "w_up": (jax.random.normal(ks[2], (eh, d, fe), jnp.float32)
                 * d ** -0.5).astype(dt),
        "w_down": (jax.random.normal(ks[3], (eh, fe, d), jnp.float32)
                   * scale_down).astype(dt),
    }
    s = {
        "router": ("embed", None),
        "w_gate": ("expert", "embed", "expert_ff"),
        "w_up": ("expert", "embed", "expert_ff"),
        "w_down": ("expert", "expert_ff", "embed"),
    }
    if m.d_ff_shared:
        shared_p, shared_s = mlp_init(ks[4], cfg, d_ff=m.d_ff_shared)
        p["shared"] = shared_p
        s["shared"] = shared_s
    return p, s


def moe_apply(params: dict, x: jax.Array, cfg: ArchConfig,
              dropless: bool = False) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (y (B, S, D), aux_loss scalar).

    Dispatch is GROUPED on the batch dimension (GShard's G axis): each
    sequence dispatches into its own (E, Cg, D) sub-buffer, so under
    batch-sharded data parallelism the scatter, expert matmul and combine
    all stay shard-local — the ungrouped version scatter-added into a
    REPLICATED (E, C, D) buffer, which XLA lowered as ~400 GB of per-layer
    all-reduce (§Perf#3).  Capacity is per-group: Cg = ceil(S·k/E · cf).

    dropless=True sizes Cg at the worst case (every token of the group to
    one expert) — used at decode time where groups are single tokens and a
    drop would change served logits.

    Of a share of the experts (`MoEConfig.n_held` < `n_experts`), only the
    held ones are dispatched and computed; y is their part of the result
    plus the shared expert's."""
    m: MoEConfig = cfg.moe
    b, s, d = x.shape
    k, e = m.top_k, m.n_held

    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32),
                        params["router"])                       # (G, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # (G, S, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses -------------------------------------------------------
    # load-balance: E * sum_e (fraction of tokens to e) * (mean prob of e)
    chosen = jax.nn.one_hot(expert_ids[..., 0], m.n_experts,
                            dtype=jnp.float32)
    load = chosen.mean((0, 1))
    importance = probs.mean((0, 1))
    aux = m.aux_coef * m.n_experts * jnp.sum(load * importance)
    aux = aux + m.router_z_coef * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- grouped sort-based capacity dispatch ------------------------------
    cap = s if dropless else int(max(1, (s * k // m.n_experts)
                                     * m.capacity_factor))
    flat_e = expert_ids.reshape(b, s * k)                       # (G, S*k)
    n_bins = e
    if e < m.n_experts:
        # ids of the held experts from 0; the rest sort last, in a bin of
        # their own, and go to the drop slot
        flat_e = flat_e - m.first_held
        held = (flat_e >= 0) & (flat_e < e)
        flat_e = jnp.where(held, flat_e, e)
        n_bins = e + 1
    order = jnp.argsort(flat_e, axis=1)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    counts = jax.vmap(lambda fe: jnp.bincount(fe, length=n_bins))(flat_e)
    starts = jnp.cumsum(counts, axis=1) - counts                # (G, E)
    pos_sorted = jnp.arange(s * k)[None, :] \
        - jnp.take_along_axis(starts, sorted_e, axis=1)
    pos = jnp.zeros((b, s * k), jnp.int32).at[
        jnp.arange(b)[:, None], order].set(pos_sorted.astype(jnp.int32))
    keep = pos < cap                                            # (G, S*k)
    if e < m.n_experts:
        keep &= held
    dst = jnp.where(keep, flat_e * cap + pos, e * cap)          # drop slot

    token_idx = jnp.repeat(jnp.arange(s), k)[None, :]           # (1, S*k)
    token_idx = jnp.broadcast_to(token_idx, (b, s * k))
    gidx = jnp.arange(b)[:, None]
    xk = jnp.take_along_axis(x, token_idx[..., None], axis=1)   # (G, S*k, D)
    xk = ctx.shard_batch(xk)
    buf = jnp.zeros((b, e * cap + 1, d), x.dtype)
    buf = ctx.shard_batch(buf)
    buf = buf.at[gidx, dst].add(xk * keep[..., None].astype(x.dtype))
    buf = ctx.shard_batch(buf)
    buf = buf[:, :-1].reshape(b, e, cap, d)
    buf = ctx.shard_spec(buf, "batch", None, None, "model")

    # ---- batched expert SwiGLU (weights broadcast over groups) -------------
    # bf16 end-to-end with f32 only inside the nonlinearity: keeps the
    # backward ff-contraction all-reduces in bf16 (halves §Perf#3c volume)
    g = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])
    g = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
    u = jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    y = jnp.einsum("gecf,efd->gecd", g * u,
                   params["w_down"])                            # (G,E,Cg,D)
    y = ctx.shard_batch(y)

    # ---- combine -----------------------------------------------------------
    y_flat = jnp.concatenate([y.reshape(b, e * cap, d),
                              jnp.zeros((b, 1, d), y.dtype)], axis=1)
    picked = ctx.shard_batch(
        jnp.take_along_axis(y_flat, dst[..., None], axis=1))
    w = (gate_vals.reshape(b, s * k) * keep).astype(x.dtype)
    out = ctx.shard_batch(jnp.zeros((b, s, d), x.dtype))
    out = out.at[gidx, token_idx].add(picked * w[..., None])

    if m.d_ff_shared:
        out = out + mlp(params["shared"], x.reshape(b * s, d)
                        ).reshape(b, s, d)
    return out, aux
