"""Data pipeline + serving engine over objcache."""

import json

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_reduced
from repro.data import TokenPipeline, synth_corpus_to_cos
from repro.models import build_model
from repro.serving import ModelStore, ServingEngine
from repro.train import train_state_init
from conftest import make_cluster, make_fs


def test_pipeline_deterministic_and_cache_warms(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    synth_corpus_to_cos(cl.cos, "b", "corpus", n_shards=3,
                        tokens_per_shard=4 * 33 * 4, vocab=100)
    pipe = TokenPipeline(fs, "/b/corpus", batch=4, seq_len=32)
    b1 = [b["tokens"].copy() for b in pipe.batches(epoch=0)]
    t_cold = cl.clock.now
    b2 = [b["tokens"].copy() for b in pipe.batches(epoch=0)]
    t_warm = cl.clock.now - t_cold
    assert len(b1) == len(b2) > 0
    for a, b in zip(b1, b2):
        np.testing.assert_array_equal(a, b)
    assert t_warm < t_cold          # second epoch hits the cache tiers
    # labels shift by one within the packed stream
    batch = next(iter(pipe.batches(epoch=0)))
    assert batch["tokens"].shape == (4, 32)
    assert batch["labels"].shape == (4, 32)
    cl.close()


def test_model_store_and_engine_generate(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    cfg = get_reduced("qwen3-0.6b")
    model = build_model(cfg)
    state, _ = train_state_init(model, jax.random.PRNGKey(0), max_seq=64)
    CheckpointManager(fs, "/b/models/m").save(0, state.params, durable=True)

    store = ModelStore(fs, "/b/models/m")
    params, nbytes = store.load(0, like=state.params)
    assert nbytes > 0
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    engine = ServingEngine(model, params, max_len=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=5, dtype=np.int32)
               for _ in range(3)]
    outs = engine.generate(prompts, max_new=4)
    assert len(outs) == 3 and all(len(o) == 4 for o in outs)
    assert all(0 <= t < cfg.vocab for o in outs for t in o)
    cl.close()


_LOADERS = {
    "model_store": lambda fs, root, like: ModelStore(fs, root).load(
        0, like=like)[0],
    "checkpoint": lambda fs, root, like: CheckpointManager(fs, root).restore(
        0, like=like),
}


@pytest.mark.parametrize("loader", sorted(_LOADERS))
def test_model_store_load_missing_leaf_and_dtype_mismatch(workdir, loader):
    """A manifest that drops a leaf or lies about a dtype must fail loudly
    (named leaf in the message), never deserialize garbage: the model
    store reads through the checkpoint's one reader, and so does a
    training resume."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    cfg = get_reduced("qwen3-0.6b")
    model = build_model(cfg)
    state, _ = train_state_init(model, jax.random.PRNGKey(0), max_seq=32)
    CheckpointManager(fs, "/b/models/m").save(0, state.params)

    def load():
        return _LOADERS[loader](fs, "/b/models/m", state.params)
    man_path = "/b/models/m/step_0/manifest.json"
    manifest = json.loads(fs.read_file(man_path))
    victim = sorted(manifest["leaves"])[0]

    # missing manifest leaf
    broken = {"step": 0, "leaves": {k: v for k, v in
                                    manifest["leaves"].items()
                                    if k != victim}}
    fs.write_file(man_path, json.dumps(broken).encode())
    with pytest.raises(ValueError, match="missing leaves") as ei:
        load()
    assert victim in str(ei.value)

    # dtype mismatch: manifest claims a wider dtype than the bytes on disk
    lied = json.loads(json.dumps(manifest))
    lied["leaves"][victim]["dtype"] = "float64"
    fs.write_file(man_path, json.dumps(lied).encode())
    with pytest.raises(ValueError, match="bytes on disk") as ei:
        load()
    assert victim in str(ei.value)

    # restored manifest loads fine again
    fs.write_file(man_path, json.dumps(manifest).encode())
    params = load()
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    cl.close()


def test_cold_vs_warm_model_load_times(workdir):
    """Fig. 11 trend: cluster-warm load must beat the cold COS load."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    cfg = get_reduced("granite-8b")
    model = build_model(cfg)
    state, _ = train_state_init(model, jax.random.PRNGKey(0), max_seq=32)
    CheckpointManager(fs, "/b/models/g").save(0, state.params, durable=True)
    # evict cluster-local state by scaling to zero and restarting
    for nm in list(cl.node_list()):
        cl.remove_node(nm)
    cl2 = make_cluster(workdir + "-2", n=3)
    cl2.cos = cl.cos
    for s in cl2.servers.values():
        s.cos = cl.cos
    fs2 = make_fs(cl2, consistency="weak")
    store = ModelStore(fs2, "/b/models/g")
    t0 = cl2.clock.now
    store.load(0, like=state.params)
    cold = cl2.clock.now - t0
    t0 = cl2.clock.now
    store.load(0, like=state.params)
    warm = cl2.clock.now - t0
    assert warm < cold
    cl2.close()
    cl.close()
