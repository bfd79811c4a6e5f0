"""Model zoo: one facade over the decoder-only LM and the enc-dec backbone.

`build_model(cfg)` returns a `Model` with a uniform functional surface used
by train/serve/launch:

    params, spec = model.init(key, max_seq)
    loss         = model.loss(params, batch)
    logits       = model.prefill(params, batch)
    logits, c2   = model.decode(params, token, cache, cache_len)
    spec_tree    = model.cache_spec(batch_size, max_len)
    axes_tree    = model.cache_pos_axes()   # per leaf: position axis or None
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax

from . import encdec, lm
from .config import ArchConfig, MoEConfig, SSMConfig, ShapeConfig, SHAPES


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable[..., tuple[dict, dict]]
    loss: Callable[..., jax.Array]
    prefill: Callable[..., jax.Array]
    decode: Callable[..., tuple[jax.Array, dict]]
    cache_spec: Callable[..., dict]
    init_cache: Callable[..., dict]
    cache_pos_axes: Callable[[], dict]


def build_model(cfg: ArchConfig) -> Model:
    if cfg.family == "audio":
        return Model(
            cfg=cfg,
            init=lambda key, max_seq=4096: encdec.init_params(key, cfg,
                                                              max_seq),
            loss=lambda p, batch: encdec.loss_fn(p, cfg, batch),
            prefill=lambda p, batch: encdec.prefill_fn(p, cfg, batch),
            decode=lambda p, tok, cache, n: encdec.decode_fn(p, cfg, tok,
                                                             cache, n),
            cache_spec=lambda b, s: encdec.cache_spec(cfg, b, s),
            init_cache=lambda b, s: encdec.init_cache(cfg, b, s),
            cache_pos_axes=lambda: encdec.cache_pos_axes(cfg),
        )
    return Model(
        cfg=cfg,
        init=lambda key, max_seq=4096: lm.init_params(key, cfg),
        loss=lambda p, batch: lm.loss_fn(p, cfg, batch),
        prefill=lambda p, batch: lm.prefill_fn(p, cfg, batch),
        decode=lambda p, tok, cache, n: lm.decode_fn(p, cfg, tok, cache, n),
        cache_spec=lambda b, s: lm.cache_spec(cfg, b, s),
        init_cache=lambda b, s: lm.init_cache(cfg, b, s),
        cache_pos_axes=lambda: lm.cache_pos_axes(cfg),
    )


__all__ = ["ArchConfig", "MoEConfig", "Model", "SHAPES", "SSMConfig",
           "ShapeConfig", "build_model"]
