"""KV-cache persistence over ObjcacheFS (serving/kvstore.py).

Numpy-only store semantics (hashing, snapshot/lookup contract, bit-exact
round-trips, shape adaptation, layer-ranged reads) plus the JAX serving
integration: the same prompt must emit identical tokens with and without
KV-prefix reuse, including across a simulated scale-down/warm-restart."""

import numpy as np
import pytest

from repro.serving.kvstore import KVCacheStore, prefix_key
from conftest import make_cluster, make_fs


def _synthetic_cache(nper=2, batch=2, kv_len=32, seed=0):
    """A cache-shaped pytree mirroring models.lm.init_cache: an attention
    slot (bf16-ish halves) and an SSM slot (f32 state)."""
    rng = np.random.default_rng(seed)
    return {
        "slot0": {
            "k": rng.standard_normal((nper, batch, 2, kv_len, 8)
                                     ).astype(np.float16),
            "v": rng.standard_normal((nper, batch, 2, kv_len, 8)
                                     ).astype(np.float16),
        },
        "slot1": {
            "conv": rng.standard_normal((nper, batch, 3, 24)
                                        ).astype(np.float16),
            "ssm": rng.standard_normal((nper, batch, 4, 8, 8)
                                       ).astype(np.float32),
        },
    }


# position axis of each `_synthetic_cache` leaf, as `Model.cache_pos_axes`
_POS_AXES = {"slot0": {"k": 3, "v": 3},
             "slot1": {"conv": None, "ssm": None}}


def _live_cache(cache_len, **kw):
    """`_synthetic_cache` as a decode step leaves it: K/V zero at and past
    `cache_len`, state dense."""
    cache = _synthetic_cache(**kw)
    for leaf in ("k", "v"):
        cache["slot0"][leaf][:, :, :, cache_len:] = 0
    return cache


def _files(man):
    """The blocks files, relative to the store root, that `man`'s pieces
    lie in."""
    return {pc["file"] for seg in man["layers"] for pc in seg["pieces"]}


def _same_bytes(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    assert a.tobytes() == b.tobytes()


def test_prefix_key_dtype_stable():
    toks = [5, 1, 400, 7]
    assert prefix_key(toks) == prefix_key(np.asarray(toks, np.int64))
    assert prefix_key(toks) == prefix_key(np.asarray(toks, np.int32))
    assert prefix_key(toks) != prefix_key(toks[:-1])


def test_snapshot_and_candidate_lens():
    kv = KVCacheStore.__new__(KVCacheStore)
    kv.block_tokens = 16
    assert kv.snapshot_lens(48) == [16, 32, 47]
    assert kv.snapshot_lens(16) == [15]
    assert kv.snapshot_lens(1) == []
    assert kv.candidate_lens(47) == [47, 32, 16]
    assert kv.candidate_lens(16) == [16]
    assert kv.candidate_lens(0) == []


def test_put_get_roundtrip_bitexact(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=16)
    cache = _synthetic_cache(kv_len=32)
    toks = np.arange(32, dtype=np.int32)
    man = kv.put(toks, cache, batch_index=1)
    assert man is not None and man["cache_len"] == 32
    # second put of the same prefix is a no-op (immutable blocks)
    assert kv.put(toks, cache, batch_index=0) is None
    got, man2 = kv.get(man["key"], like=cache)
    assert man2["nbytes"] == man["nbytes"]
    for path in ("slot0/k", "slot0/v", "slot1/conv", "slot1/ssm"):
        a, b = path.split("/")
        stored = got[a][b]
        assert stored.shape[1] == 1            # batch-1 restore
        np.testing.assert_array_equal(stored[:, 0], cache[a][b][:, 1])
    cl.close()


def test_lookup_longest_prefix_and_cap(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=16)
    cache = _synthetic_cache(kv_len=64)
    prompt = np.arange(100, 148, dtype=np.int32)        # 48 tokens
    for ln in kv.snapshot_lens(48):                      # 16, 32, 47
        kv.put(prompt[:ln], cache)
    assert kv.lookup(prompt, cap=47) == (47, prefix_key(prompt[:47]))
    # a different continuation past 32 still reuses the 32-block
    other = np.concatenate([prompt[:40], np.full(8, 9999, np.int32)])
    assert kv.lookup(other, cap=39)[0] == 32
    # diverging before the first block: miss
    assert kv.lookup(np.full(48, 7, np.int32), cap=47) is None
    cl.close()


def test_get_adapts_kv_axis_and_rejects_bad(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    cache = _synthetic_cache(kv_len=32)
    toks = np.arange(16, dtype=np.int32)   # cache_len 16 < kv_len 32
    man = kv.put(toks, cache)
    # reader with a larger max_len: kv axis zero-padded, live range exact
    bigger = _synthetic_cache(kv_len=48, seed=1)
    got, _ = kv.get(man["key"], like=bigger)
    assert got["slot0"]["k"].shape[3] == 48
    np.testing.assert_array_equal(got["slot0"]["k"][:, 0, :, :32],
                                  cache["slot0"]["k"][:, 0])
    assert not got["slot0"]["k"][:, 0, :, 32:].any()
    # reader with a smaller max_len that still covers cache_len: sliced
    smaller = _synthetic_cache(kv_len=24, seed=2)
    got, _ = kv.get(man["key"], like=smaller)
    assert got["slot0"]["k"].shape[3] == 24
    # wrapped cache (cache_len == kv_len) cannot be resized
    full = kv.put(np.arange(32, dtype=np.int32), cache)
    with pytest.raises(ValueError, match="resize"):
        kv.get(full["key"], like=smaller)
    # dtype mismatch is an error, not a cast
    wrong = _synthetic_cache(kv_len=32)
    wrong["slot1"]["ssm"] = wrong["slot1"]["ssm"].astype(np.float16)
    with pytest.raises(ValueError, match="dtype"):
        kv.get(man["key"], like=wrong)
    cl.close()


def test_layer_subset_uses_ranged_reads(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv")
    cache = _synthetic_cache(kv_len=32)
    man = kv.put(np.arange(8, dtype=np.int32), cache)
    got, _ = kv.get(man["key"], layers={"slot1/ssm"})
    assert list(got) == ["slot1"] and list(got["slot1"]) == ["ssm"]
    np.testing.assert_array_equal(got["slot1"]["ssm"][:, 0],
                                  cache["slot1"]["ssm"][:, 0])
    # the subset read fetched only that leaf's blocks
    ssm_bytes = cache["slot1"]["ssm"][:, 0].nbytes
    assert kv.stats["get_bytes"] == ssm_bytes < man["nbytes"]
    cl.close()


def test_manifest_published_atomically(workdir):
    """A prefix directory without a renamed-in manifest is invisible."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv")
    toks = np.arange(8, dtype=np.int32)
    key = prefix_key(toks)
    fs.makedirs(f"/b/kv/{key}")
    fs.write_file(f"/b/kv/{key}/blocks.bin", b"garbage")
    assert kv.lookup(toks, cap=8) is None
    assert not kv.has(toks)
    cl.close()


def test_put_with_pos_axes_stores_live_positions(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    cache = _live_cache(12, kv_len=32)
    man = kv.put(np.arange(12, dtype=np.int32), cache, batch_index=1,
                 pos_axes=_POS_AXES)
    row = {f"{a}/{b}": x[:, 1] for a, leaves in cache.items()
           for b, x in leaves.items()}
    dead = 2 * row["slot0/k"][:, :, 12:].nbytes           # K and V tails
    live = sum(a.nbytes for a in row.values()) - dead
    assert man["nbytes"] == kv.stats["put_bytes"] == live
    assert kv.stats["trimmed_bytes"] == dead
    assert kv.stats["state_bytes"] == row["slot1/conv"].nbytes \
        + row["slot1/ssm"].nbytes
    for seg in man["layers"]:
        want = list(row[seg["leaf"]].shape[1:])
        assert seg["full_shape"] == want
        (piece,) = seg["pieces"]
        if seg["leaf"].startswith("slot0/"):
            want[1] = 12                  # (heads, positions, head_dim)
            assert seg["axis"] == 1 and piece["pos"] == [0, 12]
        else:
            assert seg["axis"] is None and piece["pos"] is None
        assert piece["shape"] == want
    (own,) = _files(man)
    assert own.startswith(f"{man['key']}/")
    assert len(fs.read_file(f"/b/kv/{own}")) == live
    cl.close()


@pytest.mark.parametrize("with_like", [True, False])
def test_trimmed_get_equals_full_source(workdir, with_like):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    cache = _live_cache(12, kv_len=32)
    man = kv.put(np.arange(12, dtype=np.int32), cache, batch_index=1,
                 pos_axes=_POS_AXES)
    assert kv.stats["trimmed_bytes"] > 0
    got, _ = kv.get(man["key"], like=cache if with_like else None)
    for a, b in (("slot0", "k"), ("slot0", "v"), ("slot1", "conv"),
                 ("slot1", "ssm")):
        _same_bytes(got[a][b], cache[a][b][:, 1:2])    # zero tail included
    assert kv.stats["get_bytes"] == man["nbytes"]
    cl.close()


@pytest.mark.parametrize("cache_len", [32, 40])
def test_state_and_wrapped_ring_stored_whole(workdir, cache_len):
    """SSM leaves have no position axis; a ring whose cache_len reached its
    extent (32) has no dead positions: nothing is trimmed."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    cache = _synthetic_cache(kv_len=32)
    man = kv.put(np.arange(cache_len, dtype=np.int32), cache,
                 pos_axes=_POS_AXES)
    assert kv.stats["trimmed_bytes"] == 0
    assert kv.stats["state_bytes"] == cache["slot1"]["conv"][:, 0].nbytes \
        + cache["slot1"]["ssm"][:, 0].nbytes    # the ring counts as K/V
    assert man["nbytes"] == sum(cache[a][b][:, 0].nbytes for a, b in
                                (("slot0", "k"), ("slot0", "v"),
                                 ("slot1", "conv"), ("slot1", "ssm")))
    assert all(pc["pos"] is None and pc["shape"] == seg["full_shape"]
               for seg in man["layers"] for pc in seg["pieces"])
    got, _ = kv.get(man["key"], like=cache)
    _same_bytes(got["slot0"]["k"], cache["slot0"]["k"][:, :1])
    with pytest.raises(ValueError, match="resize"):   # wrapped: no resize
        kv.get(man["key"], like=_synthetic_cache(kv_len=24))
    cl.close()


def test_snapshot_without_axes_still_restores(workdir):
    """A snapshot put without `pos_axes` is stored whole and restores,
    with and without `like`, at the writer's and at another `max_len`."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    cache = _live_cache(12, kv_len=32)
    man = kv.put(np.arange(12, dtype=np.int32), cache)
    assert kv.stats["trimmed_bytes"] == 0
    for like in (cache, None, _live_cache(12, kv_len=48, seed=1)):
        got, _ = kv.get(man["key"], like=like)
        kv_len = 32 if like is None else like["slot0"]["k"].shape[3]
        assert got["slot0"]["k"].shape[3] == kv_len
        _same_bytes(got["slot0"]["k"][:, :, :, :32],
                    cache["slot0"]["k"][:, :1])
        assert not got["slot0"]["k"][:, :, :, 32:].any()
        _same_bytes(got["slot1"]["ssm"], cache["slot1"]["ssm"][:, :1])
    cl.close()


def test_trimmed_layer_subset_ranged_reads(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    cache = _live_cache(12, kv_len=32)
    man = kv.put(np.arange(12, dtype=np.int32), cache, pos_axes=_POS_AXES)
    got, _ = kv.get(man["key"], layers={"slot0/v"}, like=cache)
    assert list(got) == ["slot0"] and list(got["slot0"]) == ["v"]
    _same_bytes(got["slot0"]["v"], cache["slot0"]["v"][:, :1])
    # only the live positions of that leaf were read
    assert kv.stats["get_bytes"] == cache["slot0"]["v"][:, 0, :, :12].nbytes
    cl.close()


def test_get_reads_only_shape_and_dtype_of_like(workdir):
    """`like` leaves that carry nothing but .shape/.dtype pin the target:
    no value of the reader's cache is read."""
    import types

    import jax

    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    cache = _live_cache(12, kv_len=32)
    man = kv.put(np.arange(12, dtype=np.int32), cache, pos_axes=_POS_AXES)
    bigger = _synthetic_cache(kv_len=48)
    for kind in (jax.ShapeDtypeStruct,
                 lambda shape, dtype: types.SimpleNamespace(shape=shape,
                                                            dtype=dtype)):
        like = {a: {b: kind(x.shape, x.dtype) for b, x in leaves.items()}
                for a, leaves in bigger.items()}
        got, _ = kv.get(man["key"], like=like)
        k = got["slot0"]["k"]
        assert k.shape == (2, 1, 2, 48, 8)
        _same_bytes(k[:, :, :, :32], cache["slot0"]["k"][:, :1])
        assert not k[:, :, :, 32:].any()
        _same_bytes(got["slot1"]["conv"], cache["slot1"]["conv"][:, :1])
    cl.close()


# a cache with attention K/V only, K/V beside SSM state, state only, and a
# ring: the K/V extent (32) is reached by every snapshot of its chain
_KINDS = {"attention": (("slot0",), (8, 16, 21)),
          "hybrid": (("slot0", "slot1"), (8, 16, 21)),
          "state": (("slot1",), (8, 16, 21)),
          "wrapped": (("slot0", "slot1"), (32, 40, 43))}


def _kind_cache(kind, cache_len):
    slots, _ = _KINDS[kind]
    cache = _live_cache(min(cache_len, 32), kv_len=32)
    return ({s: cache[s] for s in slots},
            {s: _POS_AXES[s] for s in slots})


def _live_bytes(cache, cache_len):
    """Bytes of batch row 0 of `cache` in its first `cache_len` positions
    (K/V) and its whole state."""
    out = 0
    for a, leaves in cache.items():
        for b, x in leaves.items():
            row = x[:, 0]
            out += row[:, :, :cache_len].nbytes if a == "slot0" \
                and cache_len < x.shape[3] else row.nbytes
    return out


def _np_cut(cuts):
    """`KVCacheStore.put`'s `cut` in numpy, keeping each window it cut as
    (start, size) in `cuts`."""
    def cut(arr, start, size, axis):
        cuts.append((start, size))
        return np.take(arr, np.arange(start, start + size), axis=axis)
    return cut


@pytest.mark.parametrize("kind", sorted(_KINDS))
@pytest.mark.parametrize("windowed", [False, True])
def test_delta_chain_restores_like_whole(workdir, kind, windowed):
    """base -> +block -> +tail: each delta put restores byte for byte as a
    whole put of the same tokens, and writes only what its base lacks."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    whole = KVCacheStore(fs, "/b/whole", block_tokens=8)
    toks = np.arange(100, 150, dtype=np.int32)
    base, live = None, 0
    for ln in _KINDS[kind][1]:
        cache, axes = _kind_cache(kind, ln)
        since = base["cache_len"] if base else 0
        cuts = []
        man = kv.put(toks[:ln], cache, pos_axes=axes, base=base,
                     cut=_np_cut(cuts) if windowed else None)
        # one block window a K/V leaf, holding every position written
        assert all(st <= since and ln <= st + size and size == 8
                   for st, size in cuts)
        assert len(cuts) == (2 if windowed and "slot0" in cache
                             and ln < 32 else 0)
        ref = whole.put(toks[:ln], cache, pos_axes=axes)
        live += _live_bytes(cache, ln)
        assert man["nbytes"] == ref["nbytes"] == _live_bytes(cache, ln)
        files = _files(man)
        for like in (cache, None):
            got, _ = kv.get(man["key"], like=like)
            want, _ = whole.get(ref["key"], like=like)
            for a, leaves in want.items():
                for b, x in leaves.items():
                    _same_bytes(got[a][b], x)
                    _same_bytes(got[a][b], cache[a][b][:, :1])
        base = man
    # put_bytes and shared_bytes add up to the snapshots' live bytes
    assert kv.stats["put_bytes"] + kv.stats["shared_bytes"] == live
    assert whole.stats["put_bytes"] == live
    assert whole.stats["shared_bytes"] == 0
    delta = kind in ("attention", "hybrid")
    assert (kv.stats["shared_bytes"] > 0) == delta
    assert len(files) == (3 if delta else 1)
    if delta:
        # the tail put wrote positions 16..20 of K/V, and the state whole
        k = cache["slot0"]["k"][:, 0]
        state = _live_bytes({s: cache[s] for s in cache if s != "slot0"}, 0)
        assert base["blocks_nbytes"] == 2 * k[:, :, 16:21].nbytes + state
        assert {f.split("/")[0] for f in files} \
            == {prefix_key(toks[:ln]) for ln in _KINDS[kind][1]}
    cl.close()


def test_layer_subset_reads_across_files(workdir):
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    toks = np.arange(100, 150, dtype=np.int32)
    base = None
    for ln in (8, 16, 21):
        base = kv.put(toks[:ln], _live_cache(ln), pos_axes=_POS_AXES,
                      base=base)
    cache = _live_cache(21)
    kv.stats["get_bytes"] = 0
    got, _ = kv.get(base["key"], layers={"slot0/v"}, like=cache)
    assert list(got) == ["slot0"] and list(got["slot0"]) == ["v"]
    _same_bytes(got["slot0"]["v"], cache["slot0"]["v"][:, :1])
    # one ranged read of each of the three files: V's live positions
    assert kv.stats["get_bytes"] == cache["slot0"]["v"][:, 0, :, :21].nbytes
    cl.close()


@pytest.mark.parametrize("base_toks", ["diverged", "longer", "no_tokens",
                                       "axis_less"])
def test_base_not_a_prefix_is_refused(workdir, base_toks):
    """A base that is not a proper prefix, or whose leaves were stored
    whole (put without `pos_axes`), is no base: everything is written."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    toks = np.arange(100, 150, dtype=np.int32)
    other = {"diverged": np.arange(8, dtype=np.int32),
             "longer": toks[:20], "no_tokens": toks[:8],
             "axis_less": toks[:8]}[base_toks]
    base = kv.put(other, _live_cache(len(other)), pos_axes=(
        None if base_toks == "axis_less" else _POS_AXES))
    if base_toks == "no_tokens":
        base = {k: v for k, v in base.items() if k != "tokens"}
    kv.stats["put_bytes"] = 0
    cache = _live_cache(13)
    man = kv.put(toks[:13], cache, pos_axes=_POS_AXES, base=base)
    assert kv.stats["shared_bytes"] == 0
    assert kv.stats["put_bytes"] == man["blocks_nbytes"] == man["nbytes"] \
        == _live_bytes(cache, 13)
    (own,) = _files(man)
    assert own.startswith(f"{man['key']}/")
    got, _ = kv.get(man["key"], like=cache)
    for a, leaves in cache.items():
        for b, x in leaves.items():
            _same_bytes(got[a][b], x[:, :1])
    cl.close()


@pytest.mark.parametrize("base_len", [0, 8])
def test_window_short_of_the_range_reads_whole_leaf(workdir, base_len):
    """Where no block window holds every position a put writes (21
    positions from 0, or 13 from 8, in windows of 8), nothing is cut: the
    whole leaf is read, and the snapshot is the same."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    kv = KVCacheStore(fs, "/b/kv", block_tokens=8)
    toks = np.arange(100, 150, dtype=np.int32)
    base = kv.put(toks[:8], _live_cache(8), pos_axes=_POS_AXES) \
        if base_len else None
    cache, cuts = _live_cache(21), []
    man = kv.put(toks[:21], cache, pos_axes=_POS_AXES, base=base,
                 cut=_np_cut(cuts))
    assert cuts == []
    assert kv.stats["shared_bytes"] == \
        2 * cache["slot0"]["k"][:, 0, :, :base_len].nbytes
    got, _ = kv.get(man["key"], like=cache)
    for a, leaves in cache.items():
        for b, x in leaves.items():
            _same_bytes(got[a][b], x[:, :1])
    cl.close()


class _HookedFS:
    """An FS client that runs `hook` once, just before its first write of
    a path containing `part`: a put on another client lands in between."""

    def __init__(self, fs, part, hook):
        self._fs, self._part, self._hook = fs, part, hook

    def write_file(self, path, data):
        if self._hook is not None and self._part in path:
            hook, self._hook = self._hook, None
            hook()
        return self._fs.write_file(path, data)

    def __getattr__(self, name):
        return getattr(self._fs, name)


@pytest.mark.parametrize("between", ["/blocks.", "/.manifest."])
def test_racing_puts_of_one_prefix_restore_exactly(workdir, between):
    """Two replicas store the same 21-token prefix at once, over different
    bases (8 and 16), so their blocks differ: one's put runs whole inside
    the other's, before its blocks or before its manifest.  The published
    manifest, and a delta over each manifest either put returned, restore
    byte for byte."""
    cl = make_cluster(workdir)
    fs_b = make_fs(cl)
    toks = np.arange(100, 150, dtype=np.int32)
    kv_b = KVCacheStore(fs_b, "/b/kv", block_tokens=8)
    m8 = kv_b.put(toks[:8], _live_cache(8), pos_axes=_POS_AXES)
    m16 = kv_b.put(toks[:16], _live_cache(16), pos_axes=_POS_AXES, base=m8)
    cache = _live_cache(21)
    won = {}

    def other():
        won["b"] = kv_b.put(toks[:21], cache, pos_axes=_POS_AXES, base=m16)

    kv_a = KVCacheStore(_HookedFS(make_fs(cl),
                                  between, other), "/b/kv", block_tokens=8)
    won["a"] = kv_a.put(toks[:21], cache, pos_axes=_POS_AXES, base=m8)
    assert won["a"] is not None and won["b"] is not None
    assert won["a"]["blocks_nbytes"] != won["b"]["blocks_nbytes"]
    got, _ = kv_b.get(prefix_key(toks[:21]), like=cache)
    for a, leaves in cache.items():
        for b, x in leaves.items():
            _same_bytes(got[a][b], x[:, :1])
    # a delta over either manifest: two 29-token prompts past the prefix
    longer = _live_cache(29)
    for i, side in enumerate("ab"):
        tail = np.full(8, i, np.int32)
        man = kv_b.put(np.concatenate([toks[:21], tail]), longer,
                       pos_axes=_POS_AXES, base=won[side])
        assert _files(won[side]) < _files(man)
        got, _ = kv_b.get(man["key"], like=longer)
        for a, leaves in longer.items():
            for b, x in leaves.items():
                _same_bytes(got[a][b], x[:, :1])
    cl.close()


def test_read_file_range(fs):
    data = bytes(range(256)) * 2048            # 512 KiB, 2 chunks
    fs.write_file("/b/rng.bin", data)
    assert fs.read_file_range("/b/rng.bin", 0, 16) == data[:16]
    off = 300_000                               # crosses the chunk boundary
    assert fs.read_file_range("/b/rng.bin", off - 10, 50) == \
        data[off - 10:off + 40]
    # short read at EOF, not an error
    assert fs.read_file_range("/b/rng.bin", len(data) - 8, 64) == data[-8:]


# ---------------------------------------------------------------------------
# JAX serving integration: reuse must not change emitted tokens
# ---------------------------------------------------------------------------
def _engine(arch, fs, kv_root=None, max_len=64, block_tokens=8):
    import jax
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_reduced(arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), max_seq=max_len)
    kv = KVCacheStore(fs, kv_root, block_tokens=block_tokens) \
        if kv_root else None
    return ServingEngine(model, params, max_len=max_len, kvstore=kv), cfg


def _record_snapshots(kv, windows=None):
    """Wrap `kv.put`/`kv.get` to keep, per key, the device cache each put
    saw (as numpy) and each cache a get restored; and in `windows` the
    position extent of every device window a put cut."""
    import jax

    put_saw, got = {}, []
    real_put, real_get = kv.put, kv.get

    def put(tokens, cache, **kw):
        if windows is not None and kw.get("cut") is not None:
            real_cut = kw["cut"]

            def cut(arr, start, size, axis):
                out = real_cut(arr, start, size, axis)
                windows.append(out.shape[axis])
                return out
            kw["cut"] = cut
        man = real_put(tokens, cache, **kw)
        if man is not None:
            put_saw[man["key"]] = jax.tree.map(np.asarray, cache)
        return man

    def get(key, **kw):
        out = real_get(key, **kw)
        got.append((key, out[0]))
        return out

    kv.put, kv.get = put, get
    return put_saw, got


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-370m",
                                  "h2o-danube-3-4b", "jamba-v0.1-52b",
                                  "granite-4.0-h-small"])
def test_reuse_tokens_identical(workdir, arch):
    """Dense attention, SSM state, a sliding-window ring (window 32 at the
    reduced size, wrapped by the 40-token prompt) and two hybrids, the
    second with NoPE attention and an MoE in every layer: its snapshots mix
    trimmed K/V with whole SSM state.  A three-turn session then stores each
    turn as a delta over the snapshot it restored."""
    import jax

    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    engine, cfg = _engine(arch, fs, kv_root="/b/kv")
    windows = []
    put_saw, got = _record_snapshots(engine.kvstore, windows)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, size=21, dtype=np.int32)

    base = engine.generate([prompt], max_new=6)[0]       # no kvstore path
    cold, i_cold = engine.generate_with_reuse(prompt, max_new=6)
    assert cold == base
    assert i_cold["reused_len"] == 0 and i_cold["kv_stored"] > 0

    warm, i_warm = engine.generate_with_reuse(prompt, max_new=6)
    assert warm == base
    assert i_warm["exact_hit"] and i_warm["reused_len"] == len(prompt) - 1
    assert i_warm["prefill_steps"] == 1

    # a longer prompt sharing the prefix resumes from a block boundary
    longer = np.concatenate([prompt,
                             rng.integers(0, cfg.vocab, 9, dtype=np.int32)])
    ref = engine.generate([longer], max_new=6)[0]
    got_toks, i_long = engine.generate_with_reuse(longer, max_new=6)
    assert got_toks == ref
    assert i_long["reused_len"] >= 16        # ≥ the highest shared block

    # past the ring's window: the 39-token snapshot is a wrapped ring
    longest = np.concatenate([longer,
                              rng.integers(0, cfg.vocab, 10, dtype=np.int32)])
    ref = engine.generate([longest], max_new=6)[0]
    for _ in range(2):
        out, _ = engine.generate_with_reuse(longest, max_new=6)
        assert out == ref

    # a session: each turn is the last prompt, its answer and a new message
    turn = rng.integers(0, cfg.vocab, 13, dtype=np.int32)
    for i in range(3):
        ref = engine.generate([turn], max_new=6)[0]
        out, info = engine.generate_with_reuse(turn, max_new=6)
        assert out == ref
        assert (info["reused_len"] > 0) == (i > 0)
        turn = np.concatenate([turn, np.asarray(out, np.int32),
                               rng.integers(0, cfg.vocab, 5, dtype=np.int32)])

    # every restore, and every stored snapshot read back, is byte for byte
    # the cache its put saw, zero tail included
    kv = engine.kvstore
    spec = engine.model.cache_spec(1, engine.max_len)
    assert len(got) == 6
    restored = got + [(k, kv.get(k, like=spec)[0]) for k in put_saw]
    for key, tree in restored:
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(put_saw[key])):
            _same_bytes(a, b)
    axes = jax.tree.leaves(engine.model.cache_pos_axes(),
                           is_leaf=lambda a: a is None)
    has_positions = any(ax is not None for ax in axes)
    assert (kv.stats["trimmed_bytes"] > 0) == has_positions
    # state_bytes is the part of put_bytes with no position axis
    assert (kv.stats["state_bytes"] > 0) == (None in axes)
    assert (kv.stats["state_bytes"] < kv.stats["put_bytes"]) == has_positions
    # puts past a restore write only what their base lacks, and no put is
    # handed more of the device cache than one block window
    assert (kv.stats["shared_bytes"] > 0) == has_positions
    assert bool(windows) == has_positions
    assert all(w <= kv.block_tokens for w in windows)
    cl.close()


@pytest.mark.parametrize("path", ["generate", "cold", "warm"])
def test_one_decode_call_per_prefill_and_output_token(workdir, path):
    """A wrapper set in the engine's `_decode` after construction, as the
    benchmark sets one, sees one call per prefill step and one per output
    token after the first, on both entry points."""
    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    engine, cfg = _engine("qwen3-0.6b", fs, kv_root="/b/kv")
    prompt = np.random.default_rng(7).integers(0, cfg.vocab, 21,
                                               dtype=np.int32)
    if path == "warm":
        engine.generate_with_reuse(prompt, max_new=2)
    calls, real = [], engine._decode

    def tap(*a):
        calls.append(a[3])
        return real(*a)
    engine._decode = tap
    max_new = 5
    if path == "generate":
        (out,) = engine.generate([prompt], max_new=max_new)
        steps = len(prompt)
    else:
        out, info = engine.generate_with_reuse(prompt, max_new=max_new)
        steps = info["prefill_steps"]
        assert steps == (1 if path == "warm" else len(prompt))
    assert len(out) == max_new
    assert len(calls) == steps + max_new - 1
    # each call at the next cache position
    first = len(prompt) - steps
    assert [int(c) for c in calls] == list(range(first,
                                                 first + len(calls)))
    cl.close()


def test_warm_restart_after_scale_down(workdir):
    """Fig. 11 shape for inference state: a replica restarted over the same
    COS bucket reloads hot KV blocks and emits the same tokens."""
    import jax  # noqa: F401  (keeps the slow import grouped here)

    cl = make_cluster(workdir)
    fs = make_fs(cl, consistency="weak")
    engine, cfg = _engine("qwen3-0.6b", fs, kv_root="/b/kv")
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, 17,
                                               dtype=np.int32)
    base, _ = engine.generate_with_reuse(prompt, max_new=5)
    assert cl.drain_dirty() >= 0             # KV blocks durable in COS
    for nm in list(cl.node_list()):          # simulated scale-down
        cl.remove_node(nm)

    cl2 = make_cluster(workdir + "-2", n=3)
    cl2.cos = cl.cos
    for s in cl2.servers.values():
        s.cos = cl.cos
    fs2 = make_fs(cl2, consistency="weak")
    engine2, _ = _engine("qwen3-0.6b", fs2, kv_root="/b/kv")
    got, info = engine2.generate_with_reuse(prompt, max_new=5)
    assert got == base
    assert info["exact_hit"] and info["kv_read_bytes"] > 0
    cl2.close()
    cl.close()
