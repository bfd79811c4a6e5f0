"""Program spans (core/spans.py).  Off, a span is a shared no-op and the
modules that use spans import without JAX.  On, a `jax.profiler` trace of
the served path (reduced qwen3-0.6b and mamba2-370m, a stored prompt then
one that restores it) holds every span of the tree, each inside its
parent, and the served tokens are those of a run with spans off."""

import glob
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.core import spans
from repro.serving.kvstore import KVCacheStore
from conftest import make_cluster, make_fs

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

# each span of the served path, and the spans it may sit directly inside
PARENTS = {
    "engine.lookup": (),
    "engine.restore": (),
    "kv.get.fs": ("engine.restore",),
    "kv.get.assemble": ("engine.restore",),
    "engine.restore.to_device": ("engine.restore",),
    "engine.prefill": (),
    "kv.put": ("engine.prefill",),
    "kv.put.to_host": ("kv.put",),
    "kv.put.pack": ("kv.put",),
    "kv.put.fs": ("kv.put",),
    "engine.decode": (),
    "fs.exists": ("engine.lookup", "kv.put.fs", "fs.rename"),
    "fs.read": ("kv.get.fs",),
    "fs.write.stage": ("kv.put.fs",),
    "fs.write.commit": ("kv.put.fs",),
    "fs.rename": ("kv.put.fs",),
}


def test_span_off_is_one_shared_noop():
    assert not spans.enabled()
    a, b = spans.span("kv.put"), spans.span("fs.read")
    assert a is b
    with a as got:
        assert got is None


@pytest.mark.parametrize("module", ["repro.core.spans",
                                    "repro.serving.kvstore"])
def test_imports_without_jax(module):
    code = ("import importlib, sys\n"
            "sys.modules['jax'] = None\n"          # `import jax` now fails
            f"importlib.import_module({module!r})\n"
            "from repro.core import spans\n"
            "assert spans.span('a') is spans.span('b')\n"
            "assert not spans.enabled()\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def _session(engine, vocab: int) -> list:
    """A prompt that is stored, one that continues it and restores it,
    and the first prompt again through `generate`."""
    rng = np.random.default_rng(3)
    first = rng.integers(0, vocab, 21, dtype=np.int32)
    second = np.concatenate([first, rng.integers(0, vocab, 9,
                                                 dtype=np.int32)])
    out1, info1 = engine.generate_with_reuse(first, max_new=4)
    out2, info2 = engine.generate_with_reuse(second, max_new=4)
    assert info1["reused_len"] == 0 and info1["kv_stored"] > 0
    assert info2["reused_len"] >= 16
    return [out1, out2, engine.generate([first], max_new=4)[0]]


def _program_events(trace_dir: str) -> list[tuple[str, int, int]]:
    """(name without the prefix, start ns, end ns) of every program span
    on the trace's host plane."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((ev.name[len(spans.PREFIX):], int(ev.start_ns),
                        int(ev.start_ns + ev.duration_ns))
                       for ev in line.events
                       if ev.name.startswith(spans.PREFIX))
    return out


@pytest.fixture(scope="module", params=["qwen3-0.6b", "mamba2-370m"])
def served(request):
    """One session with spans off, then one with spans on inside a profiler
    trace, each on a fresh cluster, through one engine."""
    import jax
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_reduced(request.param)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), max_seq=64)
    engine = ServingEngine(model, params, max_len=64)
    tmp = tempfile.mkdtemp(prefix="objcache-spans-")

    def serve(name: str) -> list:
        cl = make_cluster(os.path.join(tmp, name))
        engine.kvstore = KVCacheStore(make_fs(cl, consistency="weak"),
                                      "/b/kv", block_tokens=8)
        try:
            return _session(engine, cfg.vocab)
        finally:
            cl.close()

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    try:
        off = serve("off")
        spans.enable()
        jax.profiler.start_trace(os.path.join(tmp, "trace"),
                                 profiler_options=opts)
        try:
            on = serve("on")
        finally:
            jax.profiler.stop_trace()
            spans.disable()
        events = _program_events(os.path.join(tmp, "trace"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    lowered = engine._decode.lower(params, np.zeros((1, 1), np.int32),
                                   model.init_cache(1, 64), np.int32(0))
    return {"off": off, "on": on, "events": events, "lowered": lowered}


def test_trace_holds_every_span(served):
    names = {n for n, _, _ in served["events"]}
    assert names == set(PARENTS)
    count = {n: sum(1 for m, _, _ in served["events"] if m == n)
             for n in ("engine.decode", "engine.prefill", "engine.restore")}
    # two requests with reuse and one `generate`; one restore
    assert count == {"engine.decode": 3, "engine.prefill": 3,
                     "engine.restore": 1}


def test_each_span_inside_its_parent(served):
    evs = served["events"]
    for name, s, e in evs:
        if not PARENTS[name]:
            continue
        assert any(p in PARENTS[name] and ps <= s and e <= pe
                   for p, ps, pe in evs), (name, s, e)


def test_tokens_same_with_spans_on_and_off(served):
    assert served["on"] == served["off"]
    out1, _, again = served["on"]
    assert again == out1


def test_decode_step_module_is_named(served):
    text = served["lowered"].as_text()
    assert text[text.index("module @") + len("module @"):].split()[0] \
        == "jit_decode_step"
