"""Driver of chat mixes: sessions served one request at a time through
`ServingEngine.generate_with_reuse` and a `KVCacheStore` on an objcache
mount, open loop, each request timed from its scheduled arrival.

Spans come from the benchmark's own wrappers around calls into the
program: `TimedKV` around the store's ``lookup``/``get``/``put``, and
`DecodeTap` around the engine's jitted decode step, which stamps the host
clock as each call is made.  The engine turns each token into a Python int
before it makes the next call, so the stamp of call k+1 is when token k was
out; the last token is out when ``generate_with_reuse`` returns.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from . import check
from .traffic import ChatContent, Sessions, chat_schedule, seed_words

# requests still queued this long after the window closes count as failed
DRAIN_LIMIT_S = 60.0
# served tokens the reference reads after the window, at the least
SAMPLE_TOKENS = 300


def _annotate(on: bool, name: str):
    if not on:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def timed_kv_class():
    from repro.serving import KVCacheStore

    class TimedKV(KVCacheStore):
        """The program's store, with the host seconds of each call."""

        def __init__(self, *a, annotate: bool = False, **kw) -> None:
            super().__init__(*a, **kw)
            self.annotate = annotate
            self.host_s = 0.0

        def _timed(self, name, fn, *a, **kw):
            t = time.perf_counter()
            try:
                with _annotate(self.annotate, f"bench:kv.{name}"):
                    return fn(*a, **kw)
            finally:
                self.host_s += time.perf_counter() - t

        def lookup(self, *a, **kw):
            return self._timed("lookup", super().lookup, *a, **kw)

        def get(self, *a, **kw):
            return self._timed("get", super().get, *a, **kw)

        def put(self, *a, **kw):
            return self._timed("put", super().put, *a, **kw)

    return TimedKV


class DecodeTap:
    """Stands in the engine's place of its jitted decode step; stamps the
    host clock at each call."""

    def __init__(self, fn) -> None:
        self.fn = self.jitted = fn
        self.stamps: list[float] = []

    def __call__(self, *a):
        self.stamps.append(time.perf_counter())
        return self.fn(*a)


@dataclass
class Served:
    due: float
    start: float
    prompt_len: int
    out_len: int
    reused: int = 0
    kv_s: float = 0.0
    emits: list = field(default_factory=list)       # host time per token
    calls: list = field(default_factory=list)       # (stamp, cache_len)
    ok: bool = False


def module_name(lowered) -> str:
    """The name XLA gives the module of a lowered jitted function."""
    text = lowered.as_text()
    head = text[text.index("module @") + len("module @"):]
    return head.split()[0]


def _setup(ctx):
    import jax
    from repro.launch.train import build_cache
    from repro.models import build_model
    from repro.serving import ServingEngine

    c, mod, mix = ctx.config, ctx.config_module, ctx.mix
    arch = mod.program_arch(c, ctx.arch())
    bad = mod.program_mismatches(c, arch)
    if bad:
        raise ValueError(f"{c['name']}: the program runs another config: "
                         + "; ".join(bad))
    model = build_model(arch)
    params = ctx.make_params()
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    have = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise ValueError(f"{c['name']}: the program's parameters are laid "
                         f"out otherwise than the benchmark makes them")
    workdir = tempfile.mkdtemp(prefix="bench-chat-")
    cluster, fs = build_cache(workdir)
    kv = timed_kv_class()(fs, "/train/kv", block_tokens=mix["block_tokens"],
                          annotate=ctx.trace)
    engine = ServingEngine(model, params, max_len=mix["max_len"],
                           kvstore=kv)
    tap = DecodeTap(engine._decode)
    engine._decode = tap
    return model, params, workdir, cluster, kv, engine, tap


def _warm_up(ctx, engine, content: ChatContent) -> None:
    """Store each system prompt's snapshots, then serve one request that
    restores one: every shape the window uses, the store's paths with
    them, is then compiled and in the persistent cache."""
    n_sys = ctx.mix["system_prompts"]
    for rank in range(n_sys):
        engine.generate_with_reuse(np.concatenate(
            [content.system_prompt(rank), content.warmup_token(rank)]),
            max_new=2)
    _, info = engine.generate_with_reuse(np.concatenate(
        [content.system_prompt(0), content.warmup_token(n_sys)]), max_new=2)
    if info["reused_len"] != ctx.mix["system_prompt_tokens"]:
        raise RuntimeError(f"warm-up did not restore the stored system "
                           f"prompt: {info}")


def serve_window(ctx, engine, kv, tap, content, sched, t_close) -> tuple:
    """Serve `sched` open loop; returns (served records, finished
    (prompt, tokens) pairs).  Requests due before `t_close` (seconds after
    the window opens) are all served; those still queued
    `DRAIN_LIMIT_S` after it fail."""
    sessions = Sessions(content)
    records: list[Served] = []
    finished: list = []
    t0 = time.perf_counter()
    for req in sched:
        due = t0 + req.t
        now = time.perf_counter()
        if now < due:
            with _annotate(ctx.trace, "bench:idle"):
                time.sleep(due - now)
        rec = Served(due, time.perf_counter(), req.prompt_len, req.out_len)
        records.append(rec)
        if rec.start - t0 > t_close + DRAIN_LIMIT_S:
            continue
        if req.turn and req.session not in sessions.hist:
            continue                        # its previous turn failed
        prompt = sessions.prompt(req)
        n0, kv0 = len(tap.stamps), kv.host_s
        try:
            with _annotate(ctx.trace, "bench:request"):
                out, info = engine.generate_with_reuse(prompt,
                                                       max_new=req.out_len)
        except Exception as e:                  # counted, reported, and
            print(f"request {req.idx} failed: {e!r}",   # the run goes on
                  file=sys.stderr)
            continue
        end = time.perf_counter()
        stamps = tap.stamps[n0:]
        tap.stamps.clear()
        rec.reused, rec.kv_s = info["reused_len"], kv.host_s - kv0
        first = info["prefill_steps"]
        rec.calls = [(s, rec.reused + i) for i, s in enumerate(stamps)]
        rec.emits = stamps[first:] + [end]
        rec.ok = (len(out) == req.out_len and len(rec.emits) == len(out)
                  and all(0 <= t < engine.model.cfg.vocab for t in out))
        if rec.ok:
            sessions.answered(req, prompt, out)
            finished.append((prompt, out))
    return records, finished


def judge(gaps: np.ndarray, failed: int, limit: float) -> tuple:
    """`correct` and the numbers it was decided on: every compared token
    within `limit` of the reference's best, and no request failed."""
    checks = {
        "max_logit_gap": {"value": float(gaps.max()) if gaps.size else None,
                          "limit": limit},
        "served_tokens_checked": {"value": int(gaps.size),
                                  "limit": ">= 1"},
        "failed_requests": {"value": failed, "limit": 0},
    }
    return bool(gaps.size and gaps.max() <= limit and failed == 0), checks


def run(ctx) -> dict:
    mix, c = ctx.mix, ctx.config
    model, params, workdir, cluster, kv, engine, tap = _setup(ctx)
    try:
        content = ChatContent(mix, ctx.vocab, ctx.seed)
        _warm_up(ctx, engine, content)
        lowered = tap.jitted.lower(params, np.zeros((1, 1), np.int32),
                               model.init_cache(1, mix["max_len"]),
                               np.int32(0))
        decode_module = module_name(lowered)
        sched = chat_schedule(mix, ctx.cell["rate_rps"], ctx.seconds)
        tap.stamps.clear()
        setup_s = ctx.setup_done()
        compiles0 = ctx.meter.fresh()
        with ctx.tracing():
            records, finished = serve_window(ctx, engine, kv, tap, content,
                                             sched, ctx.seconds)
        window_compiles = ctx.meter.fresh() - compiles0
        device = ctx.device_record()
    finally:
        cluster.close()
        shutil.rmtree(workdir, ignore_errors=True)
    del engine, params, kv, tap
    gc.collect()

    ok = [r for r in records if r.ok]
    failed = len(records) - len(ok)
    ttft = [1e3 * (r.emits[0] - r.due) for r in ok]
    itl = [1e3 * g for r in ok for g in np.diff(r.emits)]
    e2e = {"setup_s": setup_s}
    if ttft:
        e2e["ttft_p95_ms"] = float(np.percentile(ttft, 95))
    if itl:
        e2e["itl_p95_ms"] = float(np.percentile(itl, 95))

    # correctness: the reference over a sample of what the window served
    rng = np.random.default_rng(seed_words(ctx.seed, 3))
    samples = check.pick_samples(finished, SAMPLE_TOKENS, rng)
    checker = check.ServedCheck(ctx.config_module, c, mix["max_len"],
                                mix["output_tokens"]["max"])
    ref_params = ctx.make_params()
    items = [check.served_item(p, o) for p, o in samples]
    gaps = checker.gaps(ref_params, items)
    del ref_params
    correct, checks = judge(gaps, failed, ctx.cell["limits"]["max_logit_gap"])
    obs = {"records": records, "decode_module": decode_module,
           "window_compiles": window_compiles}
    print(f"window: {len(records)} requests, {len(ok)} served, "
          f"{len(finished)} finished, {window_compiles} compiles; "
          f"decode module {decode_module}", file=sys.stderr)
    return {"correct": correct, "attempted": len(records), "failed": failed,
            "e2e": e2e, "device": device, "checks": checks, "obs": obs,
            "items": items}
