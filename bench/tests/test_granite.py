"""CPU tests of the granite-4.0-h-small configuration and its chat cell:
the work counts against the weights, the layout against the program's, and
whole chat runs at a reduced size, sound, broken, and against the fp8
control."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from lib import chat, check
from test_harness import _break_decode, config, run_cell
from tiny import TINY_CONFIGS, TinyCell

NAME = "granite-4.0-h-small"
CELL = NAME + ".chat_reuse"

# the program's reduced config (one period of 4 layers, attention at slot
# 2), cut to expert rank 0 of 4: experts 0-3 of the router's 16, top-4
TINY_CONFIGS[NAME] = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=1 / 16, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=32, intermediate_size=32,
    shared_intermediate_size=64, num_local_experts=4, expert_parallel_size=4,
    num_experts_per_tok=4, vocab_size=512, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"] * 4)

# its logits spread about 0.003 (logits / 16): a sound run's widest gap
# reads under 2.5e-4 and the fp8 control's over 1.4e-3 (seeds 11-13)
TINY_LIMIT = 7e-4


def tiny_cell(**kw) -> TinyCell:
    cell = TinyCell(CELL, **kw)
    cell.cell = dict(cell.cell, limits={"max_logit_gap": TINY_LIMIT})
    return cell


def test_param_count_and_work_counts():
    c, mod = config(NAME)
    shapes = jax.eval_shape(lambda: mod.make_params(c, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) == mod.param_count(c)
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves) \
        == mod.param_bytes(c)
    assert 2.41e9 < mod.param_count(c) < 2.42e9       # one rank of a stage
    assert 4.82e9 < mod.param_bytes(c) < 4.84e9
    assert mod.state_bytes(c) == 9 * (128 * 128 * 64 * 4 + 3 * 8448 * 2)
    assert mod.state_bytes(c) == 38_204_928           # the 38.2 MB
    assert mod.kv_bytes_per_position(c) == 4096       # 1 layer x 2 x 8 x 128
    assert mod.routed_per_layer(c) == 1.25            # 10 x 9 / 72
    assert mod.expert_bytes(c) == 3 * 4096 * 768 * 2
    assert mod.decode_bytes(c, 1023) - mod.decode_bytes(c, 0) \
        == 1023 * 4096
    # the least bytes read the held experts a token routes to, not all 9
    held = 10 * 9 * mod.expert_bytes(c)
    routed = round(10 * 1.25 * mod.expert_bytes(c))
    assert mod.decode_bytes(c, 0) == mod.param_bytes(c) - held + routed \
        + 2 * mod.state_bytes(c) + 4096
    assert 3.44e9 < mod.decode_bytes(c, 0) < 3.46e9
    assert mod.decode_flops(c, 0) > 2 * (mod.param_count(c) - 10 * 9 * 3
                                         * 4096 * 768)


def test_layout_and_config_are_the_programs():
    from repro.configs import get_config
    from repro.models import build_model
    c, mod = config(NAME)
    arch = mod.program_arch(c, get_config(c["program_arch"]))
    assert mod.program_mismatches(c, arch) == []
    assert (arch.n_layers, arch.moe.n_experts, arch.moe.n_held,
            arch.moe.first_held) == (10, 72, 9, 0)
    model = build_model(arch)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    have = jax.eval_shape(lambda: mod.make_params(c, jax.random.PRNGKey(0)))
    assert jax.tree.structure(want) == jax.tree.structure(have)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # a config of another depth or another expert share is told apart
    other = dict(c, expert_rank=1)
    assert any(m.startswith("first_held") for m in
               mod.program_mismatches(other, arch))


def test_chat_run_is_correct():
    res = run_cell(tiny_cell())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 5
    assert set(res["e2e"]) == {"setup_s", "ttft_p95_ms", "itl_p95_ms"}
    assert sum(r.reused for r in res["obs"]["records"]) > 0
    assert res["obs"]["window_compiles"] == 0
    assert res["checks"]["served_tokens_checked"]["value"] >= 40


@pytest.mark.parametrize("how", ["state_unchanged", "token_altered"])
def test_chat_run_with_broken_decode_is_not_correct(how, monkeypatch):
    cell = tiny_cell()
    monkeypatch.setattr(chat, "_setup", _break_decode(cell, how))
    res = run_cell(cell)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_fails_where_the_program_passes():
    """At the reduced size, on three seeds, the program's run is correct,
    and the fp8 control's tokens, put through the run's own decision at
    the cell's limit, are not."""
    cell = tiny_cell(seconds=2.0)
    limit = cell.cell["limits"]["max_logit_gap"]
    for seed in (11, 12, 13):
        cell.seed = seed
        res = run_cell(cell)
        assert res["correct"], (seed, res["checks"])
        checker = check.ServedCheck(cell.config_module, cell.config,
                                    cell.mix["max_len"],
                                    cell.mix["output_tokens"]["max"])
        params = cell.make_params()
        prog = checker.gaps(params, res["items"])
        ctrl = checker.control_gaps(params, res["items"])
        control_correct, checks = chat.judge(ctrl, res["failed"], limit)
        assert not control_correct, (seed, checks)
        assert chat.judge(prog, res["failed"], limit)[0]
        assert ctrl.max() > 3 * prog.max(), (seed, prog.max(), ctrl.max())


def test_limits_by_leaf_reads_the_control_as_calibrate_does():
    """`limits_by_leaf` rounds the weights leaf by leaf and reads the
    control's choices without the bf16 tree: the same gaps as the
    checker's own control, which holds both trees at once."""
    import limits_by_leaf
    cell = tiny_cell(seconds=2.0)
    res = run_cell(cell)
    checker = check.ServedCheck(cell.config_module, cell.config,
                                cell.mix["max_len"],
                                cell.mix["output_tokens"]["max"])
    params = cell.make_params()
    want = checker.control_gaps(params, res["items"])
    got = checker.gaps(params, limits_by_leaf.control_items(
        cell, checker, res["items"]))
    np.testing.assert_array_equal(got, want)
    assert got.max() > cell.cell["limits"]["max_logit_gap"]
