"""Serving driver: load a checkpoint through the cache tiers and serve
batched greedy generation (§6.3's Triton-startup scenario).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced

A publisher cluster saves freshly initialised params durably to COS; a new
cluster generation over the same COS then loads them cold (COS miss) on one
node and from the cluster tier on a second node.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import ARCH_IDS, get_config, get_reduced
from ..core import CosStore
from ..models import Model, build_model
from ..serving import ModelStore, ServingEngine
from .compile_cache import enable_compile_cache
from .train import build_cache, mount

MODEL_DIR = "/train/models/demo"


def publish_params(model: Model, workdir: str, seed: int = 0
                   ) -> tuple[dict, CosStore]:
    """A trainer's cluster saves params made from `seed` durably (they land
    in COS) and shuts down.  Returns the params and the COS."""
    cluster, fs = build_cache(workdir, nodes=1)
    try:
        params, _ = model.init(jax.random.PRNGKey(seed))
        CheckpointManager(fs, MODEL_DIR).save(0, params, durable=True)
    finally:
        cluster.close()
    return params, cluster.cos


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--workdir", default=None,
                    help="cluster state directory (default: a new temp dir)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family == "audio":
        print("serve driver targets LM decode; whisper path exercised in "
              "tests")
    model = build_model(cfg)
    workdir = args.workdir or tempfile.mkdtemp(prefix="objcache-serve-")
    params, cos = publish_params(model, os.path.join(workdir, "publish"))
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        params)
    del params

    cluster, fs = build_cache(os.path.join(workdir, "serve"), cos=cos)
    try:
        t0 = cluster.clock.now
        params, nbytes = ModelStore(fs, MODEL_DIR).load(0, like=like)
        print(f"model load: {nbytes / 1e6:.1f} MB in "
              f"{cluster.clock.now - t0:.3f} virtual-s (cold)")
        t0 = cluster.clock.now
        ModelStore(mount(cluster, cluster.node_list()[1]), MODEL_DIR).load(
            0, like=like)
        print(f"model load: cluster tier in {cluster.clock.now - t0:.3f} "
              f"virtual-s")

        engine = ServingEngine(model, params, max_len=64)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, size=rng.integers(4, 12),
                                dtype=np.int32) for _ in range(args.batch)]
        w0 = time.time()
        outs = engine.generate(prompts, max_new=args.max_new)
        print(f"generated {args.batch} x {args.max_new} tokens in "
              f"{time.time() - w0:.2f}s wall")
        for i, o in enumerate(outs):
            print(f"  req{i}: {o}")
    finally:
        cluster.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
