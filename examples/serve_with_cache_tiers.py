"""Serving scenario (paper §6.3): publish a model to COS, then start serving
replicas that load through the three cache tiers — cold COS miss, warm
cluster, warm node — and serve batched greedy generation.

    PYTHONPATH=src python examples/serve_with_cache_tiers.py
"""

import shutil
import sys
import tempfile

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_reduced
from repro.core import (BucketMount, ClientConfig, Cluster, ObjcacheClient,
                        ObjcacheFS, ServerConfig)
from repro.models import build_model
from repro.serving import ModelStore, ServingEngine

workdir = tempfile.mkdtemp(prefix="objcache-serve-")
try:
    cluster = Cluster(workdir, [BucketMount("models", "models")],
                      cfg=ServerConfig(chunk_size=1 << 20))
    cluster.start(3)

    def fs_on(node):
        return ObjcacheFS(ObjcacheClient(
            cluster.router, cluster.clock, node,
            ClientConfig(consistency="weak"), chunk_size=1 << 20))

    cfg = get_reduced("qwen3-0.6b")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))

    # publish: a trainer saves the model durably (lands in COS)
    pub = fs_on("n0")
    CheckpointManager(pub, "/models/qwen3-tiny").save(0, params,
                                                      durable=True)
    # wipe the cluster (fresh serving fleet, cold caches)
    for nm in list(cluster.node_list()):
        cluster.remove_node(nm)
    cluster2 = Cluster(workdir + "-serve",
                       [BucketMount("models", "models")],
                       cfg=ServerConfig(chunk_size=1 << 20),
                       cos=cluster.cos)
    cluster2.start(3)

    def load_on(node):
        fs = fs_on_2(node)
        store = ModelStore(fs, "/models/qwen3-tiny")
        t0 = cluster2.clock.now
        loaded, nbytes = store.load(0, like=params)
        return loaded, nbytes, cluster2.clock.now - t0

    def fs_on_2(node):
        return ObjcacheFS(ObjcacheClient(
            cluster2.router, cluster2.clock, node,
            ClientConfig(consistency="weak"), chunk_size=1 << 20))

    served, nbytes, t_cold = load_on("n0")      # replica 1: COS miss
    _, _, t_cluster = load_on("n1")             # replica 2: cluster tier
    _, _, t_node = load_on("n1")                # replica 2 restart: node tier
    print(f"model {nbytes / 1e6:.1f} MB | cold {t_cold:.3f}s | "
          f"cluster {t_cluster:.3f}s | node {t_node:.3f}s (virtual)")

    engine = ServingEngine(build_model(cfg), served, max_len=64)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=6, dtype=np.int32)
               for _ in range(4)]
    outs = engine.generate(prompts, max_new=6)
    for i, o in enumerate(outs):
        print(f"  request {i}: generated {o}")
    assert t_node <= t_cluster <= t_cold
finally:
    shutil.rmtree(workdir, ignore_errors=True)
    shutil.rmtree(workdir + "-serve", ignore_errors=True)
print("serve_with_cache_tiers OK")
