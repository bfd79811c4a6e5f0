"""Mamba2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

The SSD block decomposition (Dao & Gu, 2024) splits the time axis into
chunks of length L.  Within a chunk the recurrence is a masked, decay-
weighted attention-like matmul (MXU-friendly); across chunks a tiny (N, P)
state is carried.  TPU adaptation:

* the chunk axis is the innermost (sequential) grid dimension, so the
  carried state h lives in VMEM scratch — the TPU analogue of the CUDA
  implementation's inter-block state passing through global memory;
* the three matmuls per chunk — G = C Bᵀ (L×L), Y_intra = (G ∘ D) X and the
  state update Bᵀ_w X — are all MXU matmuls; with L = N = P = 128 tiles the
  kernel is compute-bound rather than memory-bound;
* decay products use log-space cumulative sums for stability (exp of
  differences instead of products of many a_t < 1).

Layouts: x (BH, S, P); loga (BH, 1, S), so its (1, chunk) block keeps the
sequence on lanes; b, c (BH, S, N).  float32 math.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, loga_ref, b_ref, c_ref, y_ref, hfin_ref, h_scr, *,
                chunk: int) -> None:
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    loga = loga_ref[0].astype(jnp.float32)    # (1, L) lane-major row
    bmat = b_ref[0].astype(jnp.float32)       # (L, N)
    cmat = c_ref[0].astype(jnp.float32)       # (L, N)
    h = h_scr[...]                            # (N, P)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = lj <= li
    # cum[i] = sum_{t<=i} log a_t as an (L, 1) column: a masked lane
    # reduction (Mosaic has no cumsum lowering)
    cum = jnp.sum(jnp.where(causal, loga, 0.0), axis=1, keepdims=True)
    total = jnp.sum(loga, axis=1, keepdims=True)             # (1, 1)

    # intra-chunk: y_i += sum_{j<=i} (c_i · b_j) exp(cum_i - cum_j) x_j;
    # cum_j along lanes is the (L, L) transpose of cum_i broadcast
    gmat = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (L, L)
    cum_i = jnp.broadcast_to(cum, (chunk, chunk))
    dmask = jnp.where(causal, jnp.exp(cum_i - cum_i.T), 0.0)
    y = jax.lax.dot_general(gmat * dmask, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(cum_i) * (c_i · h_prev)
    y += jnp.exp(cum) * jax.lax.dot_general(
        cmat, h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = exp(total) h_prev + sum_j exp(total - cum_j) b_j x_jᵀ
    w = jnp.exp(total - cum)                  # (L, 1)
    h_new = jnp.exp(total) * h + jax.lax.dot_general(
        bmat * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (N, P)
    h_scr[...] = h_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        hfin_ref[0] = h_new.astype(hfin_ref.dtype)


def ssd_scan_pallas(x: jax.Array, loga: jax.Array, b: jax.Array,
                    c: jax.Array, *, chunk: int = 128,
                    interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """x (B,S,H,P), loga (B,S,H), b/c (B,S,H,N) -> (y (B,S,H,P), h (B,H,N,P)).

    S must be a multiple of `chunk` (callers pad; the model layer pads)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    chunk = max(1, min(chunk, S))
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    xr = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    lr = jnp.moveaxis(loga, 2, 1).reshape(B * H, 1, S)
    br = jnp.moveaxis(b, 2, 1).reshape(B * H, S, N)
    cr = jnp.moveaxis(c, 2, 1).reshape(B * H, S, N)

    def seq_map(bh, ci):
        return (bh, ci, 0)

    def row_map(bh, ci):
        return (bh, 0, ci)

    def fin_map(bh, ci):
        return (bh, 0, 0)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, hfin = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), seq_map),
            pl.BlockSpec((1, 1, chunk), row_map),
            pl.BlockSpec((1, chunk, N), seq_map),
            pl.BlockSpec((1, chunk, N), seq_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), seq_map),
            pl.BlockSpec((1, N, P), fin_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xr, lr, br, cr)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    return y, hfin.reshape(B, H, N, P)
