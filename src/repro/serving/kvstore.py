"""KV-cache persistence over ObjcacheFS — inference state as a workload.

The paper's serving story (§6.3, Fig. 11) moves *parameters* through the
cache tiers; this module moves *inference state*.  ObjectCache (PAPERS.md,
arXiv 2605.22850) shows that layerwise LLM KV caches retrieved from object
storage are exactly the access shape an elastic filesystem cache
accelerates: immutable-once-written blocks, keyed by prompt prefix, read
back layer-at-a-time at the start of a request.  Writing them through
`ObjcacheFS` means they inherit everything the data path already has —
dirty tracking, background write-back (`core/flusher.py`), eviction to COS
under cache pressure, migration on ring changes, and durable survival of a
scale-to-zero drain — with no new protocol code.

Layout per stored prefix (all paths under the store root, typically a
mounted bucket directory)::

    <root>/<key>/blocks.bin       # per-layer segments, concatenated
    <root>/<key>/manifest.json    # tokens, cache_len, segment table

``key`` is a hash of the exact token prefix.  ``blocks.bin`` holds one
segment per (cache leaf, layer period) — the layerwise block granularity
ObjectCache fetches — and the manifest records each segment's
(offset, nbytes, dtype, shape, full_shape), so a reader can fetch any
single layer with one ranged read (`ObjcacheFS.read_file_range`).
``shape`` is what the segment holds and ``full_shape`` the writer's
allocated per-period shape; they differ only along a leaf's position axis
(the kv axis of attention K/V), where a snapshot keeps its first
``cache_len`` positions: the rest are the zeros `init_cache` wrote, so
they are never moved.  Recurrent state (SSM ``conv``/``ssm``) has no
position axis, and a ring buffer that has wrapped (``cache_len`` at or
past its extent) has no dead positions: both are stored whole.  A
manifest without ``full_shape`` (older writers) holds whole segments.
The manifest is committed with the same write-then-rename discipline as
`checkpoint/manager.py`: rename is a 2PC transaction, so a prefix either
has a complete manifest or is invisible — a crashed writer never publishes
a partial cache.

Snapshot/lookup contract (why prefixes are stored at *block* lengths):
SSM state (`models/mamba2.py`) is a cumulative recurrence — unlike an
attention KV cache it cannot be truncated to a shorter prefix after the
fact.  The store therefore saves snapshots only at agreed lengths
(`snapshot_lens`: every ``block_tokens``-th position plus ``prompt_len-1``,
the state that emits the first token), and `lookup` probes exactly those
lengths, longest first.  Restoring a snapshot is bit-exact: segments are
raw array bytes placed into a zeroed array of the target's shape, and a
zero tail along the kv axis is invisible to `attention_decode`'s
``cache_len`` mask (and to an unwrapped ring buffer).

This module is numpy-only on purpose: the benchmark gate
(`benchmarks/kv_smoke.py`) exercises the data path without importing JAX.
Caches are nested dicts whose leaves are arrays shaped
``(n_periods, batch, ...)`` — the layout of `models.lm.init_cache` — and
`put`/`get` move one batch row at a time.
"""

from __future__ import annotations

import hashlib
import json

import numpy as np

from ..core.fs import ObjcacheFS
from ..core.spans import span

_BLOCKS = "blocks.bin"
_MANIFEST = "manifest.json"


def prefix_key(tokens) -> str:
    """Content hash of a token prefix (dtype-pinned so python ints, int32
    and int64 arrays of the same tokens all map to the same key)."""
    raw = np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes()
    return hashlib.sha1(raw).hexdigest()[:20]


def _flat_items(tree, prefix: str = "") -> list[tuple[str, object]]:
    """Nested-dict flatten with '/'-joined paths, sorted for a stable
    segment order (no jax.tree dependency)."""
    out: list[tuple[str, object]] = []
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.extend(_flat_items(v, path))
        else:
            out.append((path, v))
    return out


def _unflatten(items: dict[str, np.ndarray]) -> dict:
    out: dict = {}
    for path, arr in items.items():
        cur = out
        parts = path.split("/")
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = arr
    return out


def _live_index(stored: tuple[int, ...], full: tuple[int, ...],
                target: tuple[int, ...], cache_len: int,
                leaf: str) -> tuple[slice, ...]:
    """Where a stored per-period segment goes in a `target`-shaped one.

    `stored`, the writer's `full` shape and `target` may disagree along one
    axis only: the position axis, cut to ``cache_len`` by the writer and
    sized to the reader's ``max_len``.  Resizing it is exact only while the
    first ``cache_len`` positions are the only live ones, i.e. the cache
    has not wrapped past the smaller of the two sizes."""
    diff = [i for i, (s, f, t) in enumerate(zip(stored, full, target))
            if not s == f == t]
    if not len(stored) == len(full) == len(target) or len(diff) > 1:
        raise ValueError(f"kv block {leaf!r}: stored shape {full} "
                         f"incompatible with target {target}")
    sel = [slice(None)] * len(target)
    if diff:
        ax = diff[0]
        if full[ax] != target[ax] and cache_len > min(full[ax], target[ax]):
            raise ValueError(
                f"kv block {leaf!r}: cannot resize axis {ax} from "
                f"{full[ax]} to {target[ax]} with cache_len="
                f"{cache_len} live positions (cache wrapped)")
        sel[ax] = slice(0, min(stored[ax], target[ax]))
    return tuple(sel)


class KVCacheStore:
    """Prefix-keyed KV/SSM-state block store over an `ObjcacheFS` mount.

    One store instance is one serving replica's view; different replicas
    (different FS clients, possibly different nodes) sharing a root see
    each other's prefixes through the cluster cache — that sharing is the
    cluster-cache tier of `benchmarks/kv_reuse.py`.
    """

    def __init__(self, fs: ObjcacheFS, root: str,
                 block_tokens: int = 16) -> None:
        assert block_tokens >= 1
        self.fs = fs
        self.root = root.rstrip("/")
        self.block_tokens = block_tokens
        # counters surfaced by benchmarks: puts/put_bytes on the write side
        # (state_bytes: the part of put_bytes in leaves with no position
        # axis, such as SSM state, the rest being position-axis K/V;
        # trimmed_bytes: dead positions past cache_len a put left out),
        # hits/misses/probes on the lookup side, get_bytes on the read side
        self.stats: dict[str, int] = {
            "puts": 0, "put_bytes": 0, "state_bytes": 0, "trimmed_bytes": 0,
            "dup_puts": 0,
            "hits": 0, "misses": 0, "probes": 0, "gets": 0, "get_bytes": 0,
        }

    # ------------------------------------------------------------------
    # snapshot/lookup length contract
    # ------------------------------------------------------------------
    def snapshot_lens(self, prompt_len: int) -> list[int]:
        """Prefix lengths worth persisting while prefilling a prompt of
        `prompt_len` tokens: every block boundary (shareable with any
        request whose prompt continues past it) plus ``prompt_len - 1``
        (the exact-hit state that emits this prompt's first token)."""
        lens = {k for k in range(self.block_tokens, prompt_len,
                                 self.block_tokens)}
        if prompt_len > 1:
            lens.add(prompt_len - 1)
        return sorted(lens)

    def candidate_lens(self, cap: int) -> list[int]:
        """Lengths `lookup` probes, longest first: `cap` itself plus every
        block boundary below it.  O(cap / block_tokens) existence probes
        bound the metadata cost of a miss."""
        out = {cap} if cap >= 1 else set()
        out.update(k for k in range(self.block_tokens, cap,
                                    self.block_tokens))
        return sorted(out, reverse=True)

    # ------------------------------------------------------------------
    # store / fetch
    # ------------------------------------------------------------------
    def _dir(self, key: str) -> str:
        return f"{self.root}/{key}"

    def has(self, tokens) -> bool:
        return self.fs.exists(f"{self._dir(prefix_key(tokens))}/{_MANIFEST}")

    def put(self, tokens, cache: dict, batch_index: int = 0,
            pos_axes: dict | None = None) -> dict | None:
        """Persist one batch row of `cache` keyed by the exact `tokens`
        prefix.  Returns the manifest, or None if this prefix is already
        stored (first writer wins; blocks are immutable once published).

        `pos_axes` (a tree like `cache`, e.g. `Model.cache_pos_axes()`)
        gives each leaf's position axis in the ``(n_periods, batch, ...)``
        layout, or None for state without one.  A leaf with a position
        axis longer than ``cache_len = len(tokens)`` is stored as its first
        ``cache_len`` positions; every other leaf, and every leaf when
        `pos_axes` is None, is stored whole."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            return None
        key = prefix_key(tokens)
        d = self._dir(key)
        axes = dict(_flat_items(pos_axes)) if pos_axes is not None else {}
        with span("kv.put"):
            with span("kv.put.fs"):
                stored = self.fs.exists(f"{d}/{_MANIFEST}")
            if stored:
                self.stats["dup_puts"] += 1
                return None
            with span("kv.put.to_host"):
                host = [(leaf, np.asarray(arr))
                        for leaf, arr in _flat_items(cache)]
            with span("kv.put.pack"):
                segs: list[dict] = []
                blobs: list[np.ndarray] = []
                off = trimmed = state = 0
                for leaf, arr in host:
                    if arr.ndim < 2 or batch_index >= arr.shape[1]:
                        raise ValueError(
                            f"cache leaf {leaf!r}: expected "
                            f"(n_periods, batch, ...), got {arr.shape}")
                    row = arr[:, batch_index]
                    full, whole = row.shape[1:], row.nbytes
                    ax = axes.get(leaf)
                    if ax is None:
                        state += whole
                    elif tokens.size < arr.shape[ax]:
                        # ax counts the batch axis, which `row` has dropped
                        row = row[(slice(None),) * (ax - 1)
                                  + (slice(0, tokens.size),)]
                    # one copy of the live positions, all periods in order
                    row = np.ascontiguousarray(row)
                    trimmed += whole - row.nbytes
                    for p in range(row.shape[0]):  # one block per period
                        segs.append({"leaf": leaf, "period": p,
                                     "offset": off, "nbytes": row[p].nbytes,
                                     "dtype": str(arr.dtype),
                                     "shape": list(row.shape[1:]),
                                     "full_shape": list(full)})
                        off += row[p].nbytes
                    blobs.append(row.reshape(-1).view(np.uint8))
                blocks = b"".join(blobs)
            with span("kv.put.fs"):
                self.fs.makedirs(d)
                self.fs.write_file(f"{d}/{_BLOCKS}", blocks)
                manifest = {"key": key, "tokens": tokens.tolist(),
                            "cache_len": int(tokens.size), "nbytes": off,
                            "n_blocks": len(segs), "layers": segs}
                tmp = f"{d}/.manifest.tmp"
                self.fs.write_file(tmp, json.dumps(manifest).encode())
                self.fs.rename(tmp, f"{d}/{_MANIFEST}")   # 2PC publish point
        self.stats["puts"] += 1
        self.stats["put_bytes"] += off
        self.stats["state_bytes"] += state
        self.stats["trimmed_bytes"] += trimmed
        return manifest

    def lookup(self, tokens, cap: int | None = None
               ) -> tuple[int, str] | None:
        """Longest stored prefix of `tokens`, probing only the snapshot
        lengths.  `cap` bounds the usable length — a serving engine passes
        ``len(prompt) - 1`` because the final prompt token must always be
        fed through decode to produce first-token logits.  Returns
        ``(length, key)`` or None."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        cap = tokens.size if cap is None else min(cap, tokens.size)
        for ln in self.candidate_lens(cap):
            self.stats["probes"] += 1
            key = prefix_key(tokens[:ln])
            if self.fs.exists(f"{self._dir(key)}/{_MANIFEST}"):
                self.stats["hits"] += 1
                return ln, key
        self.stats["misses"] += 1
        return None

    def manifest(self, key: str) -> dict:
        return json.loads(self.fs.read_file(f"{self._dir(key)}/{_MANIFEST}"))

    def get(self, key: str, like: dict | None = None,
            layers: set[str] | None = None) -> tuple[dict, dict]:
        """Fetch stored blocks for `key` as a batch-1 cache (nested dict of
        numpy arrays shaped ``(n_periods, 1, ...)``).

        `like` (a tree of the same structure whose leaves carry ``.shape``
        and ``.dtype``, e.g. the engine's freshly initialized cache or its
        `cache_spec`; no values are read) pins target shapes/dtypes: kv
        axes are zero-padded or cut to the reader's ``max_len`` where that
        is exact (see `_live_index`), and a dtype mismatch is an error, not
        a cast — a silently converted cache would break bit-determinism.
        Without `like`, each leaf comes back in the writer's full shape.
        Either way, positions a put left out read as zeros.

        `layers` optionally restricts the fetch to a subset of leaf paths
        (e.g. ``{"slot0/k"}``); each selected segment is one ranged read,
        the layerwise-retrieval pattern of ObjectCache.  Returns
        ``(cache, manifest)``."""
        with span("kv.get.fs"):
            man = self.manifest(key)
            path = f"{self._dir(key)}/{_BLOCKS}"
            want = [s for s in man["layers"]
                    if layers is None or s["leaf"] in layers]
            if layers is None:
                # whole-prefix restore: one sequential read of the blocks file
                raw_all = memoryview(self.fs.read_file(path))
            else:
                raws = [self.fs.read_file_range(path, s["offset"],
                                                s["nbytes"]) for s in want]
        with span("kv.get.assemble"):
            if layers is None:
                raws = [raw_all[s["offset"]:s["offset"] + s["nbytes"]]
                        for s in want]
            per_leaf: dict[str, list[tuple[dict, object]]] = {}
            for seg, raw in zip(want, raws):
                if len(raw) != seg["nbytes"]:
                    raise ValueError(
                        f"kv block {seg['leaf']}[{seg['period']}] of {key}: "
                        f"read {len(raw)} bytes, manifest says "
                        f"{seg['nbytes']}")
                per_leaf.setdefault(seg["leaf"], []).append((seg, raw))
                self.stats["get_bytes"] += len(raw)
            self.stats["gets"] += 1
            like_flat = dict(_flat_items(like)) if like is not None else {}
            leaves: dict[str, np.ndarray] = {}
            for leaf, parts in per_leaf.items():
                seg0 = parts[0][0]
                dtype = np.dtype(seg0["dtype"])
                full = tuple(seg0.get("full_shape", seg0["shape"]))
                shape = (len(parts),) + full
                tgt = like_flat.get(leaf)
                if tgt is not None:
                    if str(tgt.dtype) != str(dtype):
                        raise ValueError(
                            f"kv block {leaf!r}: stored dtype "
                            f"{dtype} != cache dtype {tgt.dtype}")
                    # target per-row shape: drop the batch axis
                    shape = (tgt.shape[0],) + tuple(tgt.shape[2:])
                    if shape[0] != len(parts):
                        raise ValueError(
                            f"kv block {leaf!r}: {len(parts)} stored "
                            f"periods, cache has {shape[0]}")
                stored = tuple(seg0["shape"])
                sel = _live_index(stored, full, shape[1:], man["cache_len"],
                                  leaf)
                out = np.zeros((shape[0], 1) + shape[1:], dtype)
                for seg, raw in parts:
                    src = np.frombuffer(raw, dtype=dtype).reshape(stored)
                    out[seg["period"], 0][sel] = src[sel]
                leaves[leaf] = out
            return _unflatten(leaves), man
