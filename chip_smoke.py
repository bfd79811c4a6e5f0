"""Bring-up smoke test of the main path on one TPU chip, at full width.

    python chip_smoke.py

Three phases run in this one process, which holds the chip:

* serve   — qwen3-0.6b params made from a seed are published durably
            through `CheckpointManager`; a new cluster over the same COS
            loads them cold on one node and from the cluster tier on a
            second; `ServingEngine` answers a batch of requests, repeats a
            prompt through the `KVCacheStore` and the decode path's
            first-token logits are held to `model.prefill`'s;
* train   — a few qwen3-0.6b steps of `launch/train.py`'s loop with input
            from `TokenPipeline`, one checkpoint saved and restored;
* kernels — the Pallas flash attention (qwen3-0.6b widths) and SSD scan
            (mamba2-370m widths) against `kernels/ref.py`.

The script refuses to run anywhere but on a TPU.  Any failed check raises
and exits non-zero.  The times it prints are bring-up observations, not
benchmark numbers.  The last line of stdout is one JSON object naming the
device.  The phase functions take a config, so a CPU test can rehearse
them at reduced sizes with the kernels in interpret mode.
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import MODEL_DIR, publish_params  # noqa: E402
from repro.launch.train import (CKPT_DIR, build_cache, mount,  # noqa: E402
                                train_loop)
from repro.models import build_model  # noqa: E402
from repro.models.mamba2 import _dims as ssm_dims  # noqa: E402
from repro.serving import KVCacheStore, ModelStore, ServingEngine  # noqa: E402

# decode-vs-prefill first-token logits: relative L2 error.  bf16's unit
# roundoff is 2^-8 (3.9e-3) and 28 layers of bf16 activations compound it;
# a stale or misplaced cache entry gives an error of order 1
LOGITS_TOL = 5e-2
# bf16 kernel tolerances of tests/test_kernels.py (flash: _tol, SSD: its own)
FLASH_TOL = 3e-2
SSD_TOL = 6e-2


class SmokeCheckError(Exception):
    """A bring-up check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckError(what)


def same_bytes(a, b) -> bool:
    """Two pytrees hold byte-identical leaves of the same dtypes."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.tobytes() != y.tobytes():
            return False
    return True


# =========================================================================
# phases
# =========================================================================
def serve_phase(cfg, workdir: str, *, seed: int = 0, batch: int = 4,
                max_new: int = 8, max_len: int = 256,
                reuse_prompt_len: int = 40) -> dict:
    model = build_model(cfg)
    published, cos = publish_params(model, os.path.join(workdir, "publish"),
                                    seed=seed)
    cluster, fs = build_cache(os.path.join(workdir, "serve"), cos=cos)
    try:
        before = cos.bytes_out
        params, nbytes = ModelStore(fs, MODEL_DIR).load(0, like=published)
        check(cos.bytes_out - before >= nbytes,
              f"cold load read {cos.bytes_out - before} B from COS for "
              f"{nbytes} B of params")
        check(same_bytes(params, published), "cold-loaded params differ")
        before = cos.bytes_out
        second, _ = ModelStore(mount(cluster, cluster.node_list()[1]),
                               MODEL_DIR).load(0, like=published)
        check(cos.bytes_out == before, "cluster-tier load went to COS")
        check(same_bytes(second, published), "cluster-tier params differ")
        del second, published

        engine = ServingEngine(model, params, max_len=max_len,
                               kvstore=KVCacheStore(fs, "/train/kv"))
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, cfg.vocab, size=int(n), dtype=np.int32)
                   for n in rng.integers(8, 24, size=batch)]
        outs = engine.generate(prompts, max_new=max_new)
        check(len(outs) == batch and all(
            len(o) == max_new and all(0 <= t < cfg.vocab for t in o)
            for o in outs), f"generate returned {outs}")

        prompt = rng.integers(0, cfg.vocab, size=reuse_prompt_len,
                              dtype=np.int32)
        first, info1 = engine.generate_with_reuse(prompt, max_new=max_new)
        again, info2 = engine.generate_with_reuse(prompt, max_new=max_new)
        check(info2["reused_len"] > 0, f"second request missed: {info2}")
        check(again == first, f"reuse changed tokens: {first} -> {again}")

        # first-token logits: the cached decode path vs a full forward
        decode = jax.jit(model.decode)
        cache = model.init_cache(1, max_len)
        for t in range(prompt.size):
            dec, cache = decode(params, jnp.asarray(prompt[None, t:t + 1]),
                                cache, jnp.int32(t))
        pre = jax.jit(model.prefill)(params, {"tokens": prompt[None]})
        dec = np.asarray(dec[0, -1], np.float32)
        pre = np.asarray(pre[0, -1], np.float32)
        check(np.isfinite(dec).all() and np.isfinite(pre).all(),
              "non-finite logits")
        rel = float(np.linalg.norm(dec - pre) / np.linalg.norm(pre))
        check(rel <= LOGITS_TOL, f"decode vs prefill logits: relative L2 "
              f"error {rel} > {LOGITS_TOL}")
        return {"param_bytes": nbytes, "reused_len": info2["reused_len"],
                "logits_rel_l2": rel}
    finally:
        cluster.close()


def train_phase(cfg, workdir: str, *, steps: int = 3, batch: int = 4,
                seq: int = 512) -> dict:
    cluster, fs = build_cache(workdir)
    try:
        state, metrics = train_loop(cfg, cluster, fs, steps=steps,
                                    batch=batch, seq=seq, ckpt_every=steps)
        loss = float(metrics["loss"])
        check(np.isfinite(loss), f"loss {loss}")
        check(int(state.step) == steps, f"state.step {int(state.step)}")
        restored = CheckpointManager(fs, CKPT_DIR).restore(steps, like=state)
        check(same_bytes(restored, state), "restored checkpoint differs")
        return {"loss": loss}
    finally:
        cluster.close()


def kernel_phase(attn_cfg, ssm_cfg, *, impl: str = "pallas",
                 seqs: tuple[int, ...] = (2048, 100), ssm_seq: int = 1024,
                 seed: int = 0) -> dict:
    """Each kernel against its oracle (computed at full f32 matmul
    precision); with `impl="pallas"` the compiled program must hold the
    Mosaic kernel, which rules out interpret mode."""
    rng = np.random.default_rng(seed)

    def normal(shape, dtype, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def run(kernel, oracle, args):
        compiled = jax.jit(kernel).lower(*args).compile()
        if impl == "pallas":
            check("tpu_custom_call" in compiled.as_text(),
                  f"no Mosaic kernel in {kernel}")
        got = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        return got, want

    out = {}
    hd = attn_cfg.resolved_head_dim
    for s in seqs:
        q = normal((1, attn_cfg.n_heads, s, hd), jnp.bfloat16)
        k = normal((1, attn_cfg.n_kv_heads, s, hd), jnp.bfloat16)
        v = normal((1, attn_cfg.n_kv_heads, s, hd), jnp.bfloat16)
        got, want = run(functools.partial(ops.flash_attention, impl=impl),
                        ref.flash_attention, (q, k, v))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=FLASH_TOL, rtol=FLASH_TOL)
        out[f"flash_s{s}_max_abs_diff"] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32))))

    _, nh, n, p = ssm_dims(ssm_cfg)
    x = normal((1, ssm_seq, nh, p), jnp.bfloat16)
    loga = -jnp.abs(normal((1, ssm_seq, nh), jnp.float32, 0.1))
    b = normal((1, ssm_seq, nh, n), jnp.bfloat16, 0.3)
    c = normal((1, ssm_seq, nh, n), jnp.bfloat16, 0.3)
    (gy, gh), (wy, wh) = run(
        functools.partial(ops.ssd_scan, chunk=ssm_cfg.ssm.chunk, impl=impl),
        ref.ssd_scan, (x, loga, b, c))
    for got, want in ((gy, wy), (gh, wh)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=SSD_TOL, rtol=SSD_TOL)
    out["ssd_y_max_abs_diff"] = float(jnp.max(jnp.abs(
        gy.astype(jnp.float32) - wy.astype(jnp.float32))))
    return out


# =========================================================================
# entry point
# =========================================================================
class CompileMeter:
    """Backend compile seconds (a persistent-cache load counts as one) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self) -> None:
        self.secs = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.secs, self.compiles, self.cache_hits


def run_phase(name: str, meter: CompileMeter, kind: str, fn, *args) -> None:
    c0, n0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    result = fn(*args)       # every phase ends on host values: work is done
    wall = time.perf_counter() - t0
    c1, n1, h1 = meter.snapshot()
    print(f"phase {name}: ok  wall_s={wall} compile_s={c1 - c0} "
          f"compiles={n1 - n0} cache_hits={h1 - h0}  {result}  "
          f"[bring-up observation on {kind}]", flush=True)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    kind = dev.device_kind
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    print(f"device_kind={kind} count={len(jax.devices())} "
          f"compile_cache={cache_dir}", flush=True)
    qwen = get_config("qwen3-0.6b")
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        run_phase("serve", meter, kind, serve_phase, qwen,
                  os.path.join(workdir, "serve"))
        run_phase("train", meter, kind, train_phase, qwen,
                  os.path.join(workdir, "train"))
        run_phase("kernels", meter, kind, kernel_phase, qwen,
                  get_config("mamba2-370m"))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
