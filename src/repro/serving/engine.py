"""Model serving over objcache — the paper's §6.3 use case (Triton startup).

`ModelStore.load()` pulls every model file through the mounted FS: a cold
start pays the COS fetch, a warm cluster pays the cluster-local tier, a
restarted replica on the same node pays only the node-local tier — the
three bars of Fig. 11.  It reads through `CheckpointManager.restore`, the
one reader of the checkpoint format.  `ServingEngine` then runs greedy
prefill+decode with the JAX model (real compute; reduced configs in
examples/tests).

Both entry points run one generation loop (`ServingEngine._run`): each
prompt token that is not already in the cache goes through the jitted
decode step, then the output tokens are decoded greedily.  `generate`
hands it a left-padded batch and a fresh cache.  With a `KVCacheStore`
attached, `generate_with_reuse` puts a lookup in front of it: it restores
the longest stored prefix of the prompt (per-layer attention KV and
SSM-state blocks, through the same cache tiers), starts the loop at that
snapshot's ``cache_len``, and stores new snapshots at block boundaries
from the loop's per-prefill-step hook.  A replica warm-restarting after a
scale-to-zero drain reloads params *and* hot KV blocks from COS/cluster
tiers; `benchmarks/kv_reuse.py` measures the resulting time-to-first-token
across the tiers with the Fig. 11 methodology.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
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
        """Returns (params, bytes_read): the checkpoint at `step` in
        `like`'s structure and dtypes, on the device, and the bytes of its
        leaves.  Every leaf file goes through the cache tiers, read and
        checked by `CheckpointManager.restore` (a `ValueError` naming the
        leaf on a manifest that does not match its files or does not cover
        `like`)."""
        tree = CheckpointManager(self.fs, self.root).restore(step, like=like)
        nbytes = sum(a.nbytes for a in jax.tree.leaves(tree))
        return jax.tree.map(jnp.asarray, tree), nbytes


class ServingEngine:
    """Minimal serving loop: a batch of prompts, left-padded, prefilled,
    then decoded greedily in lockstep.

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

    def _run(self, toks: np.ndarray, cache, start: int, max_new: int,
             on_prefill=None) -> list[list[int]]:
        """The generation loop.  Prefills positions ``[start, S)`` of the
        host token batch `toks` ``(b, S)`` into `cache`, which holds the
        first `start`, one token at a time through the decode step (that
        keeps the cache exact for every arch family, incl. ring buffers and
        SSM state); then decodes `max_new` tokens greedily.
        ``on_prefill(n, cache)`` runs after each prefill step, with `n`
        tokens in the cache.  Returns each row's output tokens.

        `self._decode` is looked up at each call, so a wrapper set on the
        engine after construction sees every step."""
        b, s = toks.shape
        cache_len = jnp.int32(start)
        logits = None
        with span("engine.prefill"):
            for t in range(start, s):
                logits, cache = self._decode(self.params,
                                             jnp.asarray(toks[:, t:t + 1]),
                                             cache, cache_len)
                cache_len = cache_len + 1
                if on_prefill is not None:
                    on_prefill(t + 1, cache)
        outs: list[list[int]] = [[] for _ in range(b)]
        with span("engine.decode"):
            for i in range(max_new):
                if i:
                    logits, cache = self._decode(self.params, tok, cache,
                                                 cache_len)
                    cache_len = cache_len + 1
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
                for r in range(b):
                    outs[r].append(int(tok[r, 0]))
        return outs

    def generate(self, prompts: list[np.ndarray], max_new: int = 8
                 ) -> list[list[int]]:
        assert prompts, "no requests"
        plen = max(len(p) for p in prompts)
        toks = np.zeros((len(prompts), plen), np.int32)
        for i, p in enumerate(prompts):
            toks[i, plen - len(p):] = p      # left-pad
        return self._run(toks, self.model.init_cache(len(prompts),
                                                     self.max_len),
                         0, max_new)

    def generate_with_reuse(self, prompt: np.ndarray, max_new: int = 8,
                            store: bool = True) -> tuple[list[int], dict]:
        """Single-request generation with KV-prefix reuse.

        Looks up the longest persisted prefix of `prompt` (capped at
        ``len(prompt) - 1``: the final prompt token always runs through
        decode so first-token logits exist), restores that snapshot into a
        fresh cache, and runs the generation loop from there: only the
        remaining tokens are prefilled — partial-prefill resume.  While
        prefilling, snapshots are written back at the store's block
        boundaries (and at ``len(prompt) - 1``) so later requests sharing
        the prefix start further along.  Each is a delta over the snapshot
        restored or stored last: only its block window leaves the device,
        and only the positions that base lacks are written.  The loop is
        `generate`'s, so the emitted tokens are bit-identical with and
        without reuse (tier-1 asserts this).

        Returns ``(tokens, info)``; `info` reports ``reused_len``,
        ``prefill_steps`` (tokens actually pushed through decode),
        ``exact_hit`` (only the final prompt token ran), ``kv_read_bytes``
        — the benchmark's TTFT inputs — and ``kv_stored``, the snapshots
        this call stored."""
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
        info["prefill_steps"] = s - start
        snap_lens = set(kv.snapshot_lens(s)) if (kv is not None and store) \
            else ()

        def snapshot(n, cache):
            nonlocal base
            if n not in snap_lens:
                return
            man = kv.put(prompt[:n], cache, pos_axes=self.pos_axes,
                         base=base, cut=self._cut)
            if man is None:     # stored already: extend that one
                man = kv.manifest(prefix_key(prompt[:n]))
            else:
                info["kv_stored"] += 1
            base = man

        (out,) = self._run(prompt[None, :], cache, start, max_new, snapshot)
        return out, info
