"""CPU tests of the benchmark harness: trace reduction, work counts,
traffic determinism, the refusal of a CPU, and whole chat runs at a
reduced size, sound and with the timed path broken underneath."""

from __future__ import annotations

import importlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import check, trace as tr
from lib.traffic import ChatContent, Sessions, chat_schedule
from run import BENCH, ROOT, load_json, load_module, seed_key
from tiny import TinyCell

CHAT_CELLS = ["qwen3-0.6b.chat_reuse", "mamba2-370m.chat_reuse"]


def config(name: str):
    return (load_json(BENCH, "configs", name + ".json"),
            load_module(os.path.join(BENCH, "configs", name + ".py"),
                        "cfg_" + name.replace("-", "_").replace(".", "_")))


# ---------------------------------------------------------------------------
# trace reduction, on a trace written out by hand
# ---------------------------------------------------------------------------
def hand_trace() -> tr.Trace:
    ms = 1_000_000
    t = tr.Trace()
    t.host = [(tr.WINDOW_SPAN, 0, 100 * ms),
              ("bench:kv.put", 10 * ms, 30 * ms),
              ("bench:request", 5 * ms, 80 * ms),
              ("bench:idle", 90 * ms, 10 * ms)]
    t.modules["/device:TPU:0"] = [
        ("jit__lambda(1160947)", 0, 10 * ms),           # busy 0-10
        ("jit__lambda(1160947)", 40 * ms, 10 * ms),     # busy 40-50
        ("jit__argmax(1801949)", 45 * ms, 10 * ms),     # busy 40-55
        ("jit__lambda(1160947)", 60 * ms, 20 * ms),     # busy 60-80
        ("jit__lambda(1160947)", 120 * ms, 5 * ms),     # after the window
        ("jit__lambda_2(77)", 85 * ms, 0),              # another module
    ]
    fusion = ("%fusion.1 = bf16[1024]{0} fusion(bf16[28,3072,1024] %p), "
              "kind=kLoop, calls=%fused_computation.4")
    t.ops["/device:TPU:0"] = {fusion: 12 * ms,
                              "%copy.2 = f32[4]{0} copy(f32[4] %x)": 4 * ms}
    return t


def test_trace_busy_idle_and_modules():
    t = hand_trace()
    assert tr.window_s(t) == pytest.approx(0.1)
    assert tr.busy_s(t) == pytest.approx(0.045)   # 10 + 15 + 20 ms
    evs = tr.module_events(t, "jit__lambda")
    assert [e[1] for e in evs] == [0, 40_000_000, 60_000_000]
    assert tr.top_ops(t) == [["%fusion.1 kLoop", pytest.approx(0.012)],
                             ["%copy.2", pytest.approx(0.004)]]
    gaps = dict((k, v) for k, v in tr.idle_gaps(t))
    # 10-40 lies inside kv.put (10-40) and request: the innermost wins;
    # 55-60 and 80-85 inside request only; 85-90 nothing; 90-100 idle
    assert gaps["bench:kv.put"] == pytest.approx(0.030)
    assert gaps["bench:request"] == pytest.approx(0.010)
    assert gaps["host:unannotated"] == pytest.approx(0.005)
    assert gaps["bench:idle"] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.045)


def test_decode_readers_on_hand_trace():
    c, mod = config("qwen3-0.6b")

    class Rec:
        ok = True
        calls = [(0.0, 100), (0.0, 101), (0.0, 102)]
    obs = {"trace": hand_trace(), "decode_module": "jit__lambda",
           "records": [Rec()], "config": c, "config_module": mod,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}
    read = {n: load_module(os.path.join(BENCH, "metrics", n + ".py"),
                           "m_" + n.replace(".", "_")).read
            for n in ("decode_device_ms.chat", "decode_roofline.chat",
                      "mfu.chat", "idle_share.chat")}
    assert read["decode_device_ms.chat"](obs) == pytest.approx(40 / 3)
    least = mod.decode_bytes(c, 101) / 819e9
    assert read["decode_roofline.chat"](obs) == pytest.approx(
        100 * least / (40e-3 / 3))
    assert read["mfu.chat"](obs) == pytest.approx(
        100 * mod.decode_flops(c, 101) / (40e-3 / 3) / 197e12)
    assert read["idle_share.chat"](obs) == pytest.approx(55.0)
    # nothing to read: no trace, or a trace without the module
    assert read["decode_roofline.chat"](dict(obs, trace=None)) is None
    assert read["mfu.chat"](dict(obs, decode_module="jit_other")) is None


# ---------------------------------------------------------------------------
# work counts against the hand counts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["qwen3-0.6b", "mamba2-370m"])
def test_param_count_matches_the_weights(name):
    c, mod = config(name)
    shapes = jax.eval_shape(lambda: mod.make_params(c, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == mod.param_count(c)
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(shapes))
    assert mod.decode_bytes(c, 0) >= nbytes


def test_work_counts_hand_values():
    q, qm = config("qwen3-0.6b")
    assert qm.kv_bytes_per_position(q) == 114_688     # 28 x 2 x 8 x 128 x 2
    assert qm.decode_bytes(q, 1023) - qm.decode_bytes(q, 0) \
        == 1023 * 114_688
    assert 1.18e9 < qm.param_count(q) * 2 < 1.21e9     # 1.19 GB of bf16
    m, mm = config("mamba2-370m")
    assert mm.state_bytes(m) == 48 * (32 * 128 * 64 * 4 + 3 * 2304 * 2)
    assert mm.state_bytes(m) == 50_995_200             # the 51 MB
    assert mm.decode_bytes(m, 5) == mm.decode_bytes(m, 900)
    assert 0.7e9 < mm.param_bytes(m) < 0.78e9


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def test_traffic_shape_fixed_and_content_from_seed():
    mix = load_json(BENCH, "traffic", "chat_reuse.json")
    a = chat_schedule(mix, 0.3, 51)
    assert a == chat_schedule(mix, 0.3, 51)
    assert all(r.prompt_len + r.out_len <= mix["max_len"] for r in a)
    assert any(r.turn > 0 for r in a) and any(r.turn == 0 for r in a)
    big = 2**40 + 12345
    c1, c2 = ChatContent(mix, 50304, big), ChatContent(mix, 50304, big)
    c3 = ChatContent(mix, 50304, big + 1)
    assert np.array_equal(c1.systems, c2.systems)
    assert not np.array_equal(c1.systems, c3.systems)
    assert np.array_equal(c1.user_message(a[3]), c2.user_message(a[3]))
    assert c1.systems.max() < 50304
    # continuations carry what was answered before
    s = Sessions(c1)
    first = next(r for r in a if r.turn == 0)
    p = s.prompt(first)
    s.answered(first, p, list(range(first.out_len)))
    nxt = next((r for r in a if r.session == first.session and r.turn == 1),
               None)
    if nxt is not None:
        q = s.prompt(nxt)
        assert np.array_equal(q[:p.size], p) and q.size == nxt.prompt_len


def test_seed_key_takes_any_whole_number():
    k1, k2 = seed_key(2**33 + 5), seed_key(5)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))
    assert np.array_equal(jax.random.key_data(seed_key(2**33 + 5)),
                          jax.random.key_data(k1))
    assert not np.array_equal(jax.random.key_data(seed_key(-(2**33 + 5))),
                              jax.random.key_data(k1))


# ---------------------------------------------------------------------------
# the benchmark contract around the run
# ---------------------------------------------------------------------------
def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CHAT_CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_peaks_know_the_chip_and_refuse_other_kinds():
    from lib.device import peaks
    assert peaks("TPU v5 lite") == {"bf16_flops": 197e12,
                                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        peaks("TPU v4")


def test_benchmark_json_names_files_that_exist():
    bench = load_json(ROOT, "BENCHMARK.json")
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(
            ROOT, os.path.splitext(c["file"])[0] + ".py"))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "cells",
                                           w["name"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}


# ---------------------------------------------------------------------------
# whole runs at a reduced size
# ---------------------------------------------------------------------------
def run_cell(cell: TinyCell) -> dict:
    cell.start()
    return importlib.import_module("lib.chat").run(cell)


@pytest.mark.parametrize("name", CHAT_CELLS)
def test_chat_run_is_correct(name):
    res = run_cell(TinyCell(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 5
    assert set(res["e2e"]) == {"setup_s", "ttft_p95_ms", "itl_p95_ms"}
    recs = res["obs"]["records"]
    assert sum(r.reused for r in recs) > 0            # prefixes restored
    assert res["obs"]["window_compiles"] == 0
    assert res["checks"]["served_tokens_checked"]["value"] >= 40


def _break_decode(cell, how: str):
    """Wrap the engine's decode step so the timed path is broken."""
    from lib import chat
    setup = chat._setup

    def broken_setup(ctx):
        out = setup(ctx)
        tap = out[-1]
        fn = tap.fn
        if how == "state_unchanged":
            tap.fn = lambda p, tok, cache, n: (fn(p, tok, cache, n)[0], cache)
        elif how == "token_altered":
            def altered(p, tok, cache, n):
                logits, cache2 = fn(p, tok, cache, n)
                return jnp.roll(logits, 1, axis=-1), cache2
            tap.fn = altered
        return out
    return broken_setup


@pytest.mark.parametrize("name", CHAT_CELLS)
@pytest.mark.parametrize("how", ["state_unchanged", "token_altered"])
def test_chat_run_with_broken_decode_is_not_correct(name, how, monkeypatch):
    from lib import chat
    cell = TinyCell(name)
    monkeypatch.setattr(chat, "_setup", _break_decode(cell, how))
    res = run_cell(cell)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("name", CHAT_CELLS)
def test_fp8_control_fails_where_the_program_passes(name):
    """At the reduced size, on three seeds, the program's run is correct,
    and the fp8 control's tokens in the program's place, put through the
    run's own decision at the cell's limit, are not; the control's widest
    gap lies well above the program's."""
    from lib import chat
    cell = TinyCell(name, seconds=2.0)
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


def test_metric_applies_by_workloads_or_by_what_it_moves():
    from run import metric_applies
    cell, e2e = "a.chat", {"ttft_p95_ms", "setup_s"}
    assert metric_applies({"name": "setup_s"}, cell, set())
    assert metric_applies({"name": "x", "workloads": [cell]}, cell, set())
    assert not metric_applies({"name": "x", "workloads": ["b"]}, cell, e2e)
    # a per-layer metric without `workloads` is every cell's that reports
    # the end-to-end metric it moves
    assert metric_applies({"name": "q", "moves": "ttft_p95_ms"}, cell, e2e)
    assert not metric_applies({"name": "q", "moves": "train_tokens_s"},
                              cell, e2e)

