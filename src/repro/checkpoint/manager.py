"""Transactional checkpointing on ObjcacheFS (the paper's §6.4 use case).

Layout per step under the mounted bucket::

    <root>/step_<n>/manifest.json        # tree structure, shapes, dtypes
    <root>/step_<n>/<flat.leaf.path>.bin # raw little-endian array bytes

Commit discipline: leaves are written first, then the manifest is written
to a temporary name and renamed into place — objcache's rename is a 2PC
transaction, so a checkpoint either has a complete manifest or is invisible.
Durability to COS is *write-back*: `save()` returns after the cluster-local
commit; uploads overlap subsequent compute via the background flush
(`Cluster.tick_flush`), which is exactly the asynchronous-checkpoint
advantage Fig. 12 measures against S3FS's synchronous upload-on-close.
`save(..., durable=True)` additionally fsyncs every file (Fig. 8 persisting
transactions) before returning.
"""

from __future__ import annotations

import json

import jax
import numpy as np

from ..core.fs import ObjcacheFS


def _flatten(tree):
    """(flat key, leaf) pairs; leaves stay where they are (host or device)."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield ".".join(_key_str(k) for k in path), leaf


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


class CheckpointManager:
    def __init__(self, fs: ObjcacheFS, root: str) -> None:
        self.fs = fs
        self.root = root.rstrip("/")

    # ---- save ------------------------------------------------------------
    def save(self, step: int, tree, durable: bool = False) -> dict:
        d = f"{self.root}/step_{step}"
        self.fs.makedirs(d)
        manifest = {"step": step, "leaves": {}}
        # one leaf at a time on the host: a full-width train state is
        # several GB, and it stays on the device
        for key, leaf in _flatten(tree):
            arr = np.asarray(leaf)
            path = f"{d}/{key}.bin"
            self.fs.write_file(path, arr.tobytes())
            manifest["leaves"][key] = {"shape": list(arr.shape),
                                       "dtype": str(arr.dtype)}
            if durable:
                fh = self.fs.open(path, "r+")
                self.fs.fsync(fh)
                self.fs.close(fh)
        tmp = f"{d}/.manifest.tmp"
        self.fs.write_file(tmp, json.dumps(manifest).encode())
        self.fs.rename(tmp, f"{d}/manifest.json")   # 2PC commit point
        if durable:
            fh = self.fs.open(f"{d}/manifest.json", "r+")
            self.fs.fsync(fh)
            self.fs.close(fh)
        return manifest

    # ---- restore ---------------------------------------------------------
    def latest_step(self) -> int | None:
        try:
            names = self.fs.listdir(self.root)
        except Exception:
            return None
        steps = []
        for n in names:
            if n.startswith("step_") and self.fs.exists(
                    f"{self.root}/{n}/manifest.json"):
                steps.append(int(n.split("_", 1)[1]))
        return max(steps) if steps else None

    def restore(self, step: int, like=None):
        """The tree saved at `step`: numpy arrays by flat key, or, given
        `like`, in `like`'s structure and dtypes.  Raises `ValueError`
        naming the leaf where a leaf file's bytes do not match its
        manifest's shape and dtype, or a leaf of `like` is missing from the
        manifest: a partially published checkpoint must fail loudly, not
        deserialize garbage."""
        d = f"{self.root}/step_{step}"
        manifest = json.loads(self.fs.read_file(f"{d}/manifest.json"))
        flat = {}
        for key, info in manifest["leaves"].items():
            raw = self.fs.read_file(f"{d}/{key}.bin")
            want = int(np.prod(info["shape"], dtype=np.int64)) * \
                np.dtype(info["dtype"]).itemsize
            if len(raw) != want:
                raise ValueError(
                    f"checkpoint leaf {key!r} at {d}: {len(raw)} bytes on "
                    f"disk, manifest says {info['dtype']}{info['shape']} "
                    f"= {want} bytes")
            flat[key] = np.frombuffer(raw, dtype=info["dtype"]).reshape(
                info["shape"])
        if like is None:
            return flat
        # rebuild into the structure of `like`
        leaves_like = list(_flatten(like))
        missing = [key for key, _ in leaves_like if key not in flat]
        if missing:
            raise ValueError(f"checkpoint manifest at {d} is missing "
                             f"leaves: {', '.join(missing)}")
        rebuilt = [flat[key].astype(leaf.dtype, copy=False)
                   if hasattr(leaf, "dtype") else flat[key]
                   for key, leaf in leaves_like]
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, rebuilt)

    def delete(self, step: int) -> None:
        d = f"{self.root}/step_{step}"
        for name in self.fs.listdir(d):
            self.fs.unlink(f"{d}/{name}")
        self.fs.rmdir(d)
