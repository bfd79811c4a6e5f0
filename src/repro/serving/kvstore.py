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

    <root>/<key>/blocks.<id>.bin  # the bytes one put of it added
    <root>/<key>/manifest.json    # tokens, cache_len, segment table

``key`` is a hash of the exact token prefix.  The manifest holds one
segment per (cache leaf, layer period) — the layerwise block granularity
ObjectCache fetches — with its dtype, ``full_shape`` (the writer's
allocated per-period shape), position ``axis`` and a list of pieces, each
(``file``, ``offset``, ``nbytes``, ``shape``, ``pos``): raw array bytes
at ``offset`` in some snapshot's blocks file (``file``, relative to
the root), so a reader fetches any single layer with one ranged read per
file (`ObjcacheFS.read_file_range`).  A piece with ``pos = [a, b]``
holds positions ``a`` to ``b - 1`` along the leaf's position axis (the kv
axis of attention K/V) as the first ``b - a`` positions of its stored
``shape``.  A snapshot keeps only its first ``cache_len`` positions: the
rest are the zeros `init_cache` wrote, so they are never moved.  A piece
with ``pos`` null is the whole per-period array: recurrent state (SSM
``conv``/``ssm``) has no position axis, and a ring buffer that has
wrapped (``cache_len`` at or past its extent) has no dead positions, so
both are stored whole, as is every leaf of a writer that names no
position axes.

Each put writes its blocks under a name of its own (``<id>`` is random),
so no put ever rewrites a file that a published manifest may reference:
two replicas storing the same prefix at once each write their own file,
and whichever manifest the rename publishes matches its own bytes.

A put may name a *base*: a stored snapshot whose tokens are a proper
prefix of its own.  Its blocks file then holds only the positions
``[base cache_len, cache_len)`` of each position-axis leaf, and its
segments copy the base's pieces for the rest, so a reference is one
level deep and a reader never follows a chain.  A manifest references
only files whose manifests were committed before it.  Every stored byte
still goes through the same durable write path: a delta only stops
writing the same bytes twice.  Snapshots are immutable and this store
deletes nothing; a future deleter must keep a blocks file while any
committed manifest, or a manifest a put returned, references it.
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
import uuid

import numpy as np

from ..core.fs import ObjcacheFS
from ..core.spans import span

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


def _base_refs(seg: dict | None, dtype: str, full: tuple[int, ...],
               axis: int, since: int) -> list[dict] | None:
    """The pieces of a base's segment `seg` that hold positions
    ``[0, since)`` of a leaf laid out as `dtype`, per-period `full` shape
    and position `axis`; None where the segment cannot serve, as one
    stored whole cannot."""
    if seg is None or seg["dtype"] != dtype or seg["axis"] != axis \
            or tuple(seg["full_shape"]) != full:
        return None
    end = 0
    for p in seg["pieces"]:
        if p["pos"] is None or p["pos"][0] != end:
            return None
        end = p["pos"][1]
    return seg["pieces"] if end == since else None


def _resize_axis(full: tuple[int, ...], target: tuple[int, ...],
                 cache_len: int, leaf: str) -> int | None:
    """The axis along which a stored per-period shape `full` and a
    `target` one differ, if any.

    They may disagree along one axis only: the position axis, sized to the
    writer's and the reader's ``max_len``.  Resizing it is exact only while
    the first ``cache_len`` positions are the only live ones, i.e. the
    cache has not wrapped past the smaller of the two sizes."""
    diff = [i for i, (f, t) in enumerate(zip(full, target)) if f != t]
    if len(full) != len(target) or len(diff) > 1:
        raise ValueError(f"kv block {leaf!r}: stored shape {full} "
                         f"incompatible with target {target}")
    if diff and cache_len > min(full[diff[0]], target[diff[0]]):
        ax = diff[0]
        raise ValueError(
            f"kv block {leaf!r}: cannot resize axis {ax} from "
            f"{full[ax]} to {target[ax]} with cache_len="
            f"{cache_len} live positions (cache wrapped)")
    return diff[0] if diff else None


def _placement(piece: dict, axis: int | None, full: tuple[int, ...],
               resize: int | None, target: tuple[int, ...], leaf: str
               ) -> tuple[tuple[slice, ...], tuple[slice, ...]]:
    """Where a stored piece goes in a `target`-shaped per-period array:
    ``(source index, target index)``."""
    src = [slice(None)] * len(target)
    dst = list(src)
    if piece["pos"] is None:
        if tuple(piece["shape"]) != full:
            raise ValueError(f"kv block {leaf!r}: stored shape "
                             f"{piece['shape']} incompatible with {full}")
        if resize is not None:
            src[resize] = dst[resize] = slice(0, min(full[resize],
                                                     target[resize]))
    else:
        if resize not in (None, axis):
            raise ValueError(f"kv block {leaf!r}: stored shape {full} "
                             f"incompatible with target {target}")
        a, b = piece["pos"]
        src[axis], dst[axis] = slice(0, b - a), slice(a, b)
    return tuple(src), tuple(dst)


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
        # shared_bytes: live positions a put referenced from its base
        # instead of writing; trimmed_bytes: dead positions past cache_len
        # a put left out), hits/misses/probes on the lookup side, get_bytes
        # (bytes read) on the read side
        self.stats: dict[str, int] = {
            "puts": 0, "put_bytes": 0, "state_bytes": 0, "shared_bytes": 0,
            "trimmed_bytes": 0, "dup_puts": 0,
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
            pos_axes: dict | None = None, base: dict | None = None,
            cut=None) -> dict | None:
        """Persist one batch row of `cache` keyed by the exact `tokens`
        prefix.  Returns the manifest, or None if this prefix is already
        stored (first writer wins; blocks are immutable once published).

        `pos_axes` (a tree like `cache`, e.g. `Model.cache_pos_axes()`)
        gives each leaf's position axis in the ``(n_periods, batch, ...)``
        layout, or None for state without one.  A leaf with a position
        axis longer than ``cache_len = len(tokens)`` is stored as its first
        ``cache_len`` positions; every other leaf, and every leaf when
        `pos_axes` is None, is stored whole.

        `base`, a manifest that `put` or `get` returned, makes the put a
        delta: where the base's tokens are a proper prefix of `tokens`,
        such a leaf writes only the positions past the base's
        ``cache_len`` and references the base's pieces for the rest.  Any
        other base is ignored and everything is written.

        `cut`, called as ``cut(arr, start, size, axis)``, returns positions
        ``[start, start + size)`` of a leaf along `axis`, e.g. as
        `lax.dynamic_slice_in_dim` does on the device.  With it, a
        position-axis leaf crosses to the host as the one
        ``block_tokens``-position window that holds every position the put
        writes, where one does; without it, as the whole leaf.  Snapshots
        fall on every block boundary, so over its base a put's positions
        lie in one window."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            return None
        n = int(tokens.size)
        key = prefix_key(tokens)
        d = self._dir(key)
        axes = dict(_flat_items(pos_axes)) if pos_axes is not None else {}
        since, base_segs = self._base(base, tokens)
        with span("kv.put"):
            with span("kv.put.fs"):
                stored = self.fs.exists(f"{d}/{_MANIFEST}")
            if stored:
                self.stats["dup_puts"] += 1
                return None
            # per leaf: what to copy to the host, the positions [lo, n) it
            # writes (part: False for a whole leaf) and its base's pieces
            plan = []
            for leaf, arr in _flat_items(cache):
                shape = tuple(arr.shape)
                if len(shape) < 2 or batch_index >= shape[1]:
                    raise ValueError(
                        f"cache leaf {leaf!r}: expected "
                        f"(n_periods, batch, ...), got {shape}")
                ax = axes.get(leaf)
                item = {"leaf": leaf, "src": arr, "full": shape[2:],
                        "ax": ax, "part": ax is not None and n < shape[ax],
                        "lo": 0, "start": 0, "refs": [[]] * shape[0]}
                if item["part"]:
                    refs = [_base_refs(base_segs.get((leaf, p)),
                                       str(arr.dtype), item["full"], ax - 2,
                                       since) for p in range(shape[0])]
                    if since and None not in refs:
                        item.update(lo=since, refs=refs)
                    bt = self.block_tokens
                    size = min(bt, shape[ax])
                    start = min(item["lo"] // bt * bt, shape[ax] - size)
                    if cut is not None and n <= start + size:
                        item.update(start=start,
                                    src=cut(arr, start, size, ax))
                plan.append(item)
            with span("kv.put.to_host"):
                host = [np.asarray(item["src"]) for item in plan]
            with span("kv.put.pack"):
                segs: list[dict] = []
                blobs: list[np.ndarray] = []
                off = shared = trimmed = state = 0
                wid = uuid.uuid4().hex
                blocks_file = f"{key}/blocks.{wid}.bin"
                # position-axis pieces first: what a later delta references
                # of this file is then one run of it
                for item, arr in sorted(zip(plan, host),
                                        key=lambda it: not it[0]["part"]):
                    leaf, ax, lo = item["leaf"], item["ax"], item["lo"]
                    row = arr[:, batch_index]
                    whole = arr.dtype.itemsize * row.shape[0] \
                        * int(np.prod(item["full"]))
                    if item["part"]:
                        # ax counts the batch axis, which `row` has dropped
                        row = row[(slice(None),) * (ax - 1)
                                  + (slice(lo - item["start"],
                                           n - item["start"]),)]
                        live = whole // item["full"][ax - 2] * n
                    else:
                        live = whole
                        if ax is None:
                            state += whole
                    # one copy of what is written, all periods in order
                    row = np.ascontiguousarray(row)
                    shared += live - row.nbytes
                    trimmed += whole - live
                    for p in range(row.shape[0]):  # one segment per period
                        piece = {"file": blocks_file, "offset": off,
                                 "nbytes": row[p].nbytes,
                                 "shape": list(row.shape[1:]),
                                 "pos": [lo, n] if item["part"] else None}
                        segs.append({"leaf": leaf, "period": p,
                                     "dtype": str(arr.dtype),
                                     "full_shape": list(item["full"]),
                                     "axis": None if ax is None else ax - 2,
                                     "pieces": item["refs"][p] + [piece]})
                        off += row[p].nbytes
                    blobs.append(row.reshape(-1).view(np.uint8))
                blocks = b"".join(blobs)
            with span("kv.put.fs"):
                self.fs.makedirs(d)
                self.fs.write_file(f"{self.root}/{blocks_file}", blocks)
                manifest = {"key": key, "tokens": tokens.tolist(),
                            "cache_len": n, "nbytes": off + shared,
                            "blocks_nbytes": off,
                            "n_blocks": len(segs), "layers": segs}
                tmp = f"{d}/.manifest.{wid}.tmp"
                self.fs.write_file(tmp, json.dumps(manifest).encode())
                self.fs.rename(tmp, f"{d}/{_MANIFEST}")   # 2PC publish point
        self.stats["puts"] += 1
        self.stats["put_bytes"] += off
        self.stats["shared_bytes"] += shared
        self.stats["state_bytes"] += state
        self.stats["trimmed_bytes"] += trimmed
        return manifest

    @staticmethod
    def _base(base: dict | None, tokens: np.ndarray) -> tuple[int, dict]:
        """``(cache_len, segments by (leaf, period))`` of a base whose
        tokens are a proper prefix of `tokens`; ``(0, {})`` for any
        other."""
        if base is None:
            return 0, {}
        prefix = np.asarray(base.get("tokens", ()), np.int32).reshape(-1)
        m = prefix.size
        if not (0 < m < tokens.size and base.get("cache_len") == m
                and np.array_equal(prefix, tokens[:m])):
            return 0, {}
        return m, {(s["leaf"], s["period"]): s for s in base["layers"]}

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
        is exact (see `_resize_axis`), and a dtype mismatch is an error,
        not a cast — a silently converted cache would break
        bit-determinism.  Without `like`, each leaf comes back in the
        writer's full shape.  Either way, positions a put left out read as
        zeros.

        `layers` optionally restricts the fetch to a subset of leaf paths
        (e.g. ``{"slot0/k"}``), the layerwise-retrieval pattern of
        ObjectCache.  Each file the wanted pieces lie in is read once,
        with one ranged read over those pieces.  Returns
        ``(cache, manifest)``."""
        with span("kv.get.fs"):
            man = self.manifest(key)
            want = [seg for seg in man["layers"]
                    if layers is None or seg["leaf"] in layers]
            ranges: dict[str, list[int]] = {}     # file -> [lo, hi)
            for seg in want:
                for pc in seg["pieces"]:
                    lo, hi = pc["offset"], pc["offset"] + pc["nbytes"]
                    r = ranges.setdefault(pc["file"], [lo, hi])
                    r[0], r[1] = min(r[0], lo), max(r[1], hi)
            bufs = {f: (lo, memoryview(self.fs.read_file_range(
                        f"{self.root}/{f}", lo, hi - lo)))
                    for f, (lo, hi) in ranges.items()}
        with span("kv.get.assemble"):
            self.stats["get_bytes"] += sum(len(b) for _, b in bufs.values())
            self.stats["gets"] += 1
            per_leaf: dict[str, list[dict]] = {}
            for seg in want:
                per_leaf.setdefault(seg["leaf"], []).append(seg)
            like_flat = dict(_flat_items(like)) if like is not None else {}
            leaves: dict[str, np.ndarray] = {}
            for leaf, segs in per_leaf.items():
                dtype = np.dtype(segs[0]["dtype"])
                full = tuple(segs[0]["full_shape"])
                shape = (len(segs),) + full
                tgt = like_flat.get(leaf)
                if tgt is not None:
                    if str(tgt.dtype) != str(dtype):
                        raise ValueError(
                            f"kv block {leaf!r}: stored dtype "
                            f"{dtype} != cache dtype {tgt.dtype}")
                    # target per-row shape: drop the batch axis
                    shape = (tgt.shape[0],) + tuple(tgt.shape[2:])
                    if shape[0] != len(segs):
                        raise ValueError(
                            f"kv block {leaf!r}: {len(segs)} stored "
                            f"periods, cache has {shape[0]}")
                resize = _resize_axis(full, shape[1:], man["cache_len"],
                                      leaf)
                out = np.zeros((shape[0], 1) + shape[1:], dtype)
                for seg in segs:
                    dst = out[seg["period"], 0]
                    for pc in seg["pieces"]:
                        lo, buf = bufs[pc["file"]]
                        raw = buf[pc["offset"] - lo:
                                  pc["offset"] - lo + pc["nbytes"]]
                        if len(raw) != pc["nbytes"]:
                            raise ValueError(
                                f"kv block {leaf}[{seg['period']}] of "
                                f"{key}: read {len(raw)} bytes of "
                                f"{pc['file']}, manifest says "
                                f"{pc['nbytes']}")
                        src = np.frombuffer(raw, dtype=dtype).reshape(
                            pc["shape"])
                        s_idx, d_idx = _placement(pc, seg["axis"], full,
                                                  resize, shape[1:], leaf)
                        dst[d_idx] = src[s_idx]
                leaves[leaf] = out
            return _unflatten(leaves), man
