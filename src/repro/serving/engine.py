"""Model serving over objcache — the paper's §6.3 use case (Triton startup).

`ModelStore.load()` pulls every model file through the mounted FS: a cold
start pays the COS fetch, a warm cluster pays the cluster-local tier, a
restarted replica on the same node pays only the node-local tier — the
three bars of Fig. 11.  `ServingEngine` then runs batched prefill+decode
with the JAX model (real compute; reduced configs in examples/tests).

With a `KVCacheStore` attached, the engine also persists *inference state*
(per-layer attention KV and SSM-state blocks) through the same cache
tiers: `generate_with_reuse` looks up the longest stored prefix of the
prompt, restores that snapshot (partial-prefill resume — decode continues
from the restored ``cache_len``), and saves new snapshots at block
boundaries while prefilling.  A replica warm-restarting after a
scale-to-zero drain reloads params *and* hot KV blocks from COS/cluster
tiers; `benchmarks/kv_reuse.py` measures the resulting time-to-first-token
across the tiers with the Fig. 11 methodology.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..core.fs import ObjcacheFS
from ..core.spans import span
from ..models import Model
from .kvstore import prefix_key


class ModelStore:
    """Loads checkpointed params through any FS exposing read_file/listdir
    (ObjcacheFS, S3FSLike adapter, or S3Direct adapter)."""

    def __init__(self, fs, root: str) -> None:
        self.fs = fs
        self.root = root.rstrip("/")

    def load(self, step: int, like) -> tuple[object, int]:
        """Returns (params, bytes_read).  Every leaf file goes through the
        cache tiers.  Raises `ValueError` on a manifest that does not match
        its leaf files (truncated/mismatched dtype bytes) or does not cover
        the `like` tree — a partially published checkpoint must fail loudly,
        not deserialize garbage."""
        d = f"{self.root}/step_{step}"
        manifest = json.loads(self.fs.read_file(f"{d}/manifest.json"))
        flat = {}
        nbytes = 0
        for key, info in manifest["leaves"].items():
            raw = self.fs.read_file(f"{d}/{key}.bin")
            nbytes += len(raw)
            want = int(np.prod(info["shape"], dtype=np.int64)) * \
                np.dtype(info["dtype"]).itemsize
            if len(raw) != want:
                raise ValueError(
                    f"checkpoint leaf {key!r} at {d}: {len(raw)} bytes on "
                    f"disk, manifest says {info['dtype']}{info['shape']} "
                    f"= {want} bytes")
            flat[key] = np.frombuffer(raw, dtype=info["dtype"]).reshape(
                info["shape"])
        leaves = jax.tree_util.tree_flatten_with_path(like)[0]
        from ..checkpoint.manager import _key_str
        missing = []
        rebuilt = []
        for path, leaf in leaves:
            key = ".".join(_key_str(k) for k in path)
            if key not in flat:
                missing.append(key)
                continue
            rebuilt.append(jnp.asarray(flat[key], dtype=leaf.dtype))
        if missing:
            raise ValueError(f"checkpoint manifest at {d} is missing "
                             f"leaves: {', '.join(missing)}")
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, rebuilt), nbytes


@dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new: int
    out: list = field(default_factory=list)


class ServingEngine:
    """Minimal batched serving loop: collect requests, left-align into a
    batch, prefill, then decode greedily in lockstep.

    With a `kvstore` (a `serving.kvstore.KVCacheStore`), single-request
    generation can resume from persisted prefix state: see
    `generate_with_reuse`."""

    def __init__(self, model: Model, params, max_len: int = 256,
                 kvstore=None) -> None:
        self.model = model
        self.params = params
        self.max_len = max_len
        self.kvstore = kvstore
        # snapshots keep only the live positions of these axes
        self.pos_axes = model.cache_pos_axes()

        def decode_step(params, tok, cache, cache_len):
            return model.decode(params, tok, cache, cache_len)
        # a named function, so the trace names the module jit_decode_step
        self._decode = jax.jit(decode_step)
        # a snapshot's block window, cut on the device at a traced start
        # (`KVCacheStore.put`'s `cut`): one compile per leaf shape
        self._cut = jax.jit(jax.lax.dynamic_slice_in_dim,
                            static_argnums=(2, 3))
        self._prefill_tok = jax.jit(
            lambda p, b: model.prefill(p, b))

    def generate(self, prompts: list[np.ndarray], max_new: int = 8
                 ) -> list[list[int]]:
        assert prompts, "no requests"
        b = len(prompts)
        plen = max(len(p) for p in prompts)
        toks = np.zeros((b, plen), np.int32)
        for i, p in enumerate(prompts):
            toks[i, plen - len(p):] = p      # left-pad
        cache = self.model.init_cache(b, self.max_len)

        # prefill token-by-token through the decode path (keeps the cache
        # exact for every arch family, incl. ring buffers and SSM state)
        cache_len = jnp.int32(0)
        logits = None
        with span("engine.prefill"):
            for t in range(plen):
                logits, cache = self._decode(self.params,
                                             jnp.asarray(toks[:, t:t + 1]),
                                             cache, cache_len)
                cache_len = cache_len + 1
        outs: list[list[int]] = [[] for _ in range(b)]
        with span("engine.decode"):
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            for i in range(b):
                outs[i].append(int(tok[i, 0]))
            for _ in range(max_new - 1):
                logits, cache = self._decode(self.params, tok, cache,
                                             cache_len)
                cache_len = cache_len + 1
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                for i in range(b):
                    outs[i].append(int(tok[i, 0]))
        return outs

    def generate_with_reuse(self, prompt: np.ndarray, max_new: int = 8,
                            store: bool = True) -> tuple[list[int], dict]:
        """Single-request generation with KV-prefix reuse.

        Looks up the longest persisted prefix of `prompt` (capped at
        ``len(prompt) - 1``: the final prompt token always runs through
        decode so first-token logits exist), restores that snapshot into a
        fresh cache, and prefills only the remaining tokens — partial-
        prefill resume.  While prefilling, snapshots are written back at
        the store's block boundaries (and at ``len(prompt) - 1``) so later
        requests sharing the prefix start further along.  Each is a delta
        over the snapshot restored or stored last: only its block window
        leaves the device, and only the positions that base lacks are
        written.  Decoding is
        identical to `generate` from there, so the emitted tokens are
        bit-identical with and without reuse (tier-1 asserts this).

        Returns ``(tokens, info)``; `info` reports ``reused_len``,
        ``prefill_steps`` (tokens actually pushed through decode),
        ``exact_hit`` (only the final prompt token ran), and
        ``kv_read_bytes`` — the benchmark's TTFT inputs."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        s = prompt.size
        assert 1 <= s <= self.max_len, (s, self.max_len)
        cache = self.model.init_cache(1, self.max_len)
        start = 0
        base = None             # the snapshot the next put extends
        info = {"reused_len": 0, "prefill_steps": 0, "exact_hit": False,
                "kv_read_bytes": 0, "kv_stored": 0}
        kv = self.kvstore
        if kv is not None:
            with span("engine.lookup"):
                hit = kv.lookup(prompt, cap=s - 1)
            if hit is not None:
                start, key = hit
                with span("engine.restore"):
                    restored, man = kv.get(key, like=cache)
                    with span("engine.restore.to_device"):
                        cache = jax.tree.map(
                            lambda like, a: jnp.asarray(a, like.dtype),
                            cache, restored)
                info.update(reused_len=start, exact_hit=(start == s - 1),
                            kv_read_bytes=man["nbytes"])
                base = man
        cache_len = jnp.int32(start)
        toks = prompt[None, :]
        logits = None
        snap_lens = set(kv.snapshot_lens(s)) if (kv is not None and store) \
            else ()
        with span("engine.prefill"):
            for t in range(start, s):
                logits, cache = self._decode(self.params,
                                             jnp.asarray(toks[:, t:t + 1]),
                                             cache, cache_len)
                cache_len = cache_len + 1
                info["prefill_steps"] += 1
                if (t + 1) in snap_lens and (t + 1) > start:
                    man = kv.put(prompt[:t + 1], cache,
                                 pos_axes=self.pos_axes, base=base,
                                 cut=self._cut)
                    if man is None:     # stored already: extend that one
                        man = kv.manifest(prefix_key(prompt[:t + 1]))
                    else:
                        info["kv_stored"] += 1
                    base = man
        out: list[int] = []
        with span("engine.decode"):
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            out.append(int(tok[0, 0]))
            for _ in range(max_new - 1):
                logits, cache = self._decode(self.params, tok, cache,
                                             cache_len)
                cache_len = cache_len + 1
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                out.append(int(tok[0, 0]))
        return out, info
