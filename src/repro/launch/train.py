"""End-to-end training driver with objcache-backed data + checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 50 --batch 8 --seq 128 --ckpt-every 20

Runs on the first device JAX finds (one TPU chip, or the CPU); the
production mesh path is exercised by the dry-run.  Demonstrates the
paper's loop: stream tokens through the cache FS, checkpoint transactionally
to cluster-local storage, write back to COS asynchronously, and resume from
the latest manifest after a (simulated) failure.
"""

from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import ARCH_IDS, get_config, get_reduced
from ..core import (BucketMount, ClientConfig, Cluster, CosStore,
                    ObjcacheClient, ObjcacheFS, ServerConfig)
from ..data import TokenPipeline, synth_corpus_to_cos
from ..models import Model, build_model
from ..optim import AdamWConfig
from ..train import TrainState, make_train_step, train_state_init
from .compile_cache import enable_compile_cache


def mount(cluster: Cluster, node: str) -> ObjcacheFS:
    """A weak-consistency client of `cluster` mounted on `node`."""
    return ObjcacheFS(ObjcacheClient(cluster.router, cluster.clock, node,
                                     ClientConfig(consistency="weak"),
                                     chunk_size=cluster.cfg.chunk_size))


def build_cache(workdir: str, chunk_mb: int = 1, nodes: int = 2,
                cos: CosStore | None = None
                ) -> tuple[Cluster, ObjcacheFS]:
    """A cluster over bucket "train" (on `cos` if given: a new cluster
    generation over the same object store) and a mount on its first node."""
    cluster = Cluster(workdir, [BucketMount("train", "train")],
                      cfg=ServerConfig(chunk_size=chunk_mb << 20), cos=cos)
    cluster.start(nodes)
    return cluster, mount(cluster, cluster.node_list()[0])


CKPT_DIR = "/train/ckpt"


def jit_train_step(model: Model, opt_cfg: AdamWConfig):
    """The jitted train step.  It donates the state, so the update reuses
    its buffers: at full width two copies of params + fp32 masters +
    moments do not fit in one chip's HBM."""
    return jax.jit(make_train_step(model, opt_cfg), donate_argnums=0)


def train_loop(cfg, cluster: Cluster, fs: ObjcacheFS, *, steps: int,
               batch: int, seq: int, ckpt_every: int, lr: float = 3e-4,
               resume: bool = False) -> tuple[TrainState, dict]:
    """Train `cfg` for `steps` steps on a synthetic corpus read through `fs`,
    checkpointing to `CKPT_DIR` every `ckpt_every` steps.  Returns the final
    state and the last step's metrics."""
    model = build_model(cfg)
    # synthetic corpus straight into COS; the pipeline reads it through the
    # cache (first epoch = cold tier, later epochs = cluster/node tier)
    synth_corpus_to_cos(cluster.cos, "train", "corpus", n_shards=4,
                        tokens_per_shard=batch * (seq + 1) * 8,
                        vocab=cfg.vocab)
    pipe = TokenPipeline(fs, "/train/corpus", batch=batch, seq_len=seq)
    ckpt = CheckpointManager(fs, CKPT_DIR)

    state, _spec = train_state_init(model, jax.random.PRNGKey(0),
                                    max_seq=seq)
    start_step = 0
    if resume:
        latest = ckpt.latest_step()
        if latest is not None:
            state = jax.device_put(ckpt.restore(latest, like=state))
            start_step = latest
            print(f"resumed from step {latest}")

    step_fn = jit_train_step(model, AdamWConfig(
        lr=lr, warmup_steps=10, total_steps=steps))

    it = iter(pipe.batches(epoch=0))
    epoch = 0
    metrics: dict = {}
    t0 = time.time()
    for step in range(start_step, steps):
        try:
            batch_np = next(it)
        except StopIteration:
            epoch += 1
            it = iter(pipe.batches(epoch=epoch))
            batch_np = next(it)
        if cfg.frontend is not None:
            from ..models.lm import frontend_dim
            nf = cfg.enc_seq if cfg.family == "audio" \
                else cfg.n_frontend_tokens
            batch_np["frontend"] = np.zeros(
                (batch, nf, frontend_dim(cfg)), np.float32)
        state, metrics = step_fn(state, batch_np)
        if (step + 1) % 10 == 0 or step == start_step:
            print(f"step {step + 1:5d}  loss {float(metrics['loss']):8.4f}  "
                  f"gnorm {float(metrics['grad_norm']):8.3f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"wall {time.time() - t0:6.1f}s")
        if (step + 1) % ckpt_every == 0:
            ckpt.save(step + 1, state)
            # async write-back: uploads overlap the next steps (Fig. 12)
            cluster.tick_flush(max_inodes=8)
    return state, metrics


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--workdir", default=None,
                    help="cluster state directory (default: a new temp dir)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    workdir = args.workdir or tempfile.mkdtemp(prefix="objcache-train-")
    cluster, fs = build_cache(workdir)
    try:
        train_loop(cfg, cluster, fs, steps=args.steps, batch=args.batch,
                   seq=args.seq, ckpt_every=args.ckpt_every, lr=args.lr,
                   resume=args.resume)
        cluster.drain_dirty()
        print(f"done; dirty remaining: {cluster.dirty_counts()}")
    finally:
        cluster.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
