"""Compile the main path's programs for one TPU v5e chip, without the chip.

The TPU compiler compiles for a described topology: this refuses what
interpret mode accepts (unaligned blocks, primitives Mosaic cannot lower)
and programs that do not fit the chip's 16 GiB of HBM.  Nothing runs.

The topology is described only inside the fixture: only one process at a
time may load the TPU library, and pytest-xdist workers import every test
file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models import build_model
from repro.models.mamba2 import _dims as ssm_dims
from repro.launch.train import jit_train_step
from repro.optim import AdamWConfig
from repro.train import train_state_init

HBM_BYTES = 16 << 30          # TPU v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("seq,window", [(2048, None), (100, None),
                                        (2048, 512)])
def test_flash_attention_compiles_at_qwen3_widths(one_chip, seq, window):
    cfg = get_config("qwen3-0.6b")
    hd = cfg.resolved_head_dim
    q = _spec((1, cfg.n_heads, seq, hd), jnp.bfloat16, one_chip)
    kv = _spec((1, cfg.n_kv_heads, seq, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, window=window)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-370m")
    _, nh, n, p = ssm_dims(cfg)
    b, s = 2, 1024
    compiled = jax.jit(lambda x, la, bb, cc: ssd_scan_pallas(
        x, la, bb, cc, chunk=cfg.ssm.chunk)).lower(
        _spec((b, s, nh, p), jnp.bfloat16, one_chip),
        _spec((b, s, nh), jnp.float32, one_chip),
        _spec((b, s, nh, n), jnp.bfloat16, one_chip),
        _spec((b, s, nh, n), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _place(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def _hbm_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_qwen3_decode_fits_one_chip(one_chip):
    model = build_model(get_config("qwen3-0.6b"))
    batch, max_len = 4, 256
    params = _place(jax.eval_shape(lambda k: model.init(k)[0],
                                   jax.random.PRNGKey(0)), one_chip)
    cache = _place(model.cache_spec(batch, max_len), one_chip)
    compiled = jax.jit(model.decode).lower(
        params, _spec((batch, 1), jnp.int32, one_chip), cache,
        _spec((), jnp.int32, one_chip)).compile()
    assert 0 < _hbm_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_granite_expert_rank_decode_fits_one_chip(one_chip):
    """granite-4.0-h-small as the benchmark cuts it: one 10-layer stage
    (attention at slot 5), 9 of the 72 experts held, batch 1, 1024
    positions: 4.8 GB of weights."""
    cfg = get_config("granite-4.0-h-small")
    cfg = cfg.with_(n_layers=10, moe=dataclasses.replace(
        cfg.moe, expert_ranks=8, expert_rank=0))
    model = build_model(cfg)
    params = _place(jax.eval_shape(lambda k: model.init(k)[0],
                                   jax.random.PRNGKey(0)), one_chip)
    cache = _place(model.cache_spec(1, 1024), one_chip)
    compiled = jax.jit(model.decode).lower(
        params, _spec((1, 1), jnp.int32, one_chip), cache,
        _spec((), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 4.8e9, mem      # the held weights
    assert 0 < _hbm_bytes(compiled) < HBM_BYTES, mem


def test_qwen3_train_step_fits_one_chip(one_chip):
    """The launcher's step donates the state; without that, params, fp32
    masters and both moments are held twice and overflow HBM."""
    model = build_model(get_config("qwen3-0.6b"))
    batch, seq = 4, 512
    state = _place(jax.eval_shape(
        lambda k: train_state_init(model, k, max_seq=seq)[0],
        jax.random.PRNGKey(0)), one_chip)
    tokens = _spec((batch, seq), jnp.int32, one_chip)
    compiled = jit_train_step(model, AdamWConfig(total_steps=3)).lower(
        state, {"tokens": tokens, "labels": tokens}).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0, mem
    assert 0 < _hbm_bytes(compiled) < HBM_BYTES, mem
