"""CPU tests of the program's spans in the benchmark's trace reduction
(`lib/program_spans.py`, `span_split.py`): hand values on a hand-written
trace, the benchmark's readers unchanged by program spans, device idle
time given to the innermost span, and traced runs at a reduced size."""

from __future__ import annotations

import importlib
import os

import pytest

from lib import program_spans as ps, trace as tr
from run import BENCH, load_module
from span_split import SpanCell
from test_harness import CHAT_CELLS, config, hand_trace
from tiny import TinyCell

MS = 1_000_000

# inside hand_trace's window (0-100 ms): a put inside `bench:kv.put`
# (10-40) and a decode span over two steps; a get after the window
PROGRAM = [("objcache.kv.put", 12 * MS, 26 * MS),
           ("objcache.kv.put.to_host", 12 * MS, 4 * MS),
           ("objcache.kv.put.pack", 16 * MS, 4 * MS),
           ("objcache.kv.put.fs", 20 * MS, 10 * MS),
           ("objcache.engine.decode", 38 * MS, 44 * MS),
           ("objcache.kv.get.fs", 200 * MS, 10 * MS)]


def traced_with_program() -> tr.Trace:
    t = hand_trace()
    t.host = t.host + PROGRAM
    return t


def test_span_readings_hand_values():
    t = traced_with_program()
    assert ps.span_ms_per_request(t, "kv.put.fs", 2) == pytest.approx(5.0)
    assert ps.span_ms_per_request(t, "kv.put.to_host", 2) \
        == pytest.approx(2.0)
    assert ps.span_ms_per_request(t, "kv.put.pack", 2) == pytest.approx(2.0)
    # outside the window, or never entered: nothing spent there
    assert ps.span_ms_per_request(t, "kv.get.fs", 2) == 0.0
    assert ps.span_ms_per_request(t, "engine.restore.to_device", 2) == 0.0
    # a program without spans, or no request served: nothing to read
    assert ps.span_ms_per_request(hand_trace(), "kv.put.fs", 2) is None
    assert ps.span_ms_per_request(t, "kv.put.fs", 0) is None
    assert ps.span_s(t)["objcache.kv.put"] == pytest.approx(0.026)
    assert "objcache.kv.get.fs" not in ps.span_s(t)


def test_decode_host_gap_hand_value():
    t = traced_with_program()
    # the steps at 40-50 and 60-80 start inside the decode span (38-82);
    # of the 10 ms between them `jit__argmax` runs 50-55: 5 ms idle
    assert ps.decode_host_gap_ms(t, "jit__lambda") == pytest.approx(5.0)
    assert ps.decode_host_gap_ms(t, "jit_other") is None
    assert ps.decode_host_gap_ms(hand_trace(), "jit__lambda") is None


class Rec:
    ok = True
    calls = [(0.0, 100), (0.0, 101), (0.0, 102)]
    kv_s, start, due, prompt_len, reused = 0.25, 3.0, 1.5, 600, 512


READERS = ["queue_ms.chat", "kv_host_ms.chat", "kv_reused_share.chat",
           "idle_share.chat", "decode_device_ms.chat",
           "decode_roofline.chat", "mfu.chat"]


@pytest.mark.parametrize("name", READERS)
def test_readers_unchanged_by_program_spans(name):
    c, mod = config("qwen3-0.6b")
    read = load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "m_" + name.replace(".", "_")).read
    obs = {"trace": hand_trace(), "decode_module": "jit__lambda",
           "records": [Rec()], "config": c, "config_module": mod,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}}
    without = read(obs)
    assert without is not None
    assert read(dict(obs, trace=traced_with_program())) == without


def test_idle_gaps_go_to_the_innermost_program_span():
    gaps = dict((k, v) for k, v in tr.idle_gaps(traced_with_program(),
                                                k=20))
    # 10-40 lay all on bench:kv.put; program spans now take 12-38 of it
    assert gaps["objcache.kv.put.fs"] == pytest.approx(0.010)
    assert gaps["objcache.kv.put.to_host"] == pytest.approx(0.004)
    assert gaps["objcache.kv.put.pack"] == pytest.approx(0.004)
    assert gaps["objcache.kv.put"] == pytest.approx(0.008)
    assert gaps["bench:kv.put"] == pytest.approx(0.004)
    # 55-60 and 80-82 inside the decode span, 82-85 the request's alone
    assert gaps["objcache.engine.decode"] == pytest.approx(0.007)
    assert gaps["bench:request"] == pytest.approx(0.003)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.045)


class TinySpanCell(TinyCell, SpanCell):
    pass


@pytest.mark.parametrize("name", CHAT_CELLS)
@pytest.mark.parametrize("on", [True, False])
def test_traced_run_keeps_program_spans(name, on):
    cell = TinySpanCell(name, trace=True)
    cell.program_spans = on
    cell.start()
    res = importlib.import_module("lib.chat").run(cell)
    assert res["correct"], res["checks"]
    ok = [r for r in res["obs"]["records"] if r.ok]
    td = cell.trace_data
    split = {k: ps.span_ms_per_request(td, s, len(ok))
             for k, s in ps.SPLIT.items()}
    if not on:
        assert not ps.has_spans(td)
        assert set(split.values()) == {None}
        return
    assert all(v > 0 for v in split.values()), split
    # the program's spans cover what the benchmark's wrapper times, less
    # the lookups' probes
    kv_host = 1e3 * sum(r.kv_s for r in ok) / len(ok)
    covered = sum(split[k] for k in ps.KV_HOST)
    assert 0.8 * kv_host < covered <= kv_host
