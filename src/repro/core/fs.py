"""ObjcacheFS — the POSIX-like surface applications mount (§3.2).

Path semantics follow the paper: a COS key ``a/b/c.txt`` in bucket
``bucketA`` appears as ``/bucketA/a/b/c.txt``; a key with a trailing ``/``
is a directory.  The filesystem object wraps one `ObjcacheClient` (one per
node) and implements open/read/write/fsync/close plus the namespace calls,
honoring the configured consistency model:

* strict (read-after-write): `write()` stages chunks and runs the flush
  transaction before returning; `read()` always consults cluster state.
* weak (close-to-open): `write()` buffers locally up to 128 KB; buffered
  data commits at flush pressure, fsync(), or close(); reads may serve from
  the node-local page cache; attributes validate once at open().

`fsync()` additionally runs the persisting transaction (Fig. 8) so the file
is durable in external storage when it returns.  `close()` only commits to
cluster-local cache — upload to COS happens via the background flush
(write-back, §5.2), which is what Fig. 12 measures against S3FS's
synchronous upload-on-close.
"""

from __future__ import annotations

import posixpath

from .client import ObjcacheClient, _Handle
from .spans import span
from .types import Errno, FSError, InodeKind, ROOT_INODE


def _norm(path: str) -> list[str]:
    path = posixpath.normpath("/" + path.strip())
    return [p for p in path.split("/") if p]


class ObjcacheFS:
    def __init__(self, client: ObjcacheClient) -> None:
        self.client = client

    # =====================================================================
    # path resolution
    # =====================================================================
    def resolve(self, path: str) -> int:
        ino = ROOT_INODE
        for name in _norm(path):
            ino = self.client.lookup(ino, name)
        return ino

    def resolve_parent(self, path: str) -> tuple[int, str]:
        parts = _norm(path)
        if not parts:
            raise FSError(Errno.EINVAL, "root has no parent")
        ino = ROOT_INODE
        for name in parts[:-1]:
            ino = self.client.lookup(ino, name)
        return ino, parts[-1]

    def _cos_target(self, path: str) -> tuple[str | None, str | None]:
        """Map a path to its (bucket, key) backing: the first component is a
        bucket-mount directory; the remainder is the object key."""
        parts = _norm(path)
        if not parts:
            return None, None
        try:
            bino = self.client.lookup(ROOT_INODE, parts[0])
            battr = self.client.getattr(bino, cached_ok=True)
        except FSError:
            return None, None
        bucket = battr.get("cos_bucket")
        if bucket is None:
            return None, None
        key = "/".join(parts[1:])
        return bucket, key

    # =====================================================================
    # namespace ops
    # =====================================================================
    def stat(self, path: str) -> dict:
        return self.client.getattr(self.resolve(path))

    def exists(self, path: str) -> bool:
        with span("fs.exists"):
            try:
                self.resolve(path)
                return True
            except FSError as e:
                if e.errno in (Errno.ENOENT, Errno.ENOTDIR):
                    return False
                raise

    def listdir(self, path: str) -> list[str]:
        ino = self.resolve(path)
        return sorted(self.client.readdir(ino))

    def mkdir(self, path: str) -> int:
        parent, name = self.resolve_parent(path)
        bucket, key = self._cos_target(path)
        cos_key = (key + "/") if (bucket and key) else None
        return self.client.create(parent, name, InodeKind.DIR, bucket, cos_key)

    def makedirs(self, path: str) -> None:
        parts = _norm(path)
        for i in range(1, len(parts) + 1):
            sub = "/" + "/".join(parts[:i])
            if not self.exists(sub):
                self.mkdir(sub)

    def unlink(self, path: str) -> None:
        parent, name = self.resolve_parent(path)
        ino = self.client.lookup(parent, name)
        self.client.unlink(parent, name, ino)

    def rmdir(self, path: str) -> None:
        self.unlink(path)

    def rename(self, src: str, dst: str) -> None:
        with span("fs.rename"):
            sp, sn = self.resolve_parent(src)
            dp, dn = self.resolve_parent(dst)
            ino = self.client.lookup(sp, sn)
            if self.exists(dst):
                self.unlink(dst)
            _, new_key = self._cos_target(dst)
            self.client.rename(sp, sn, dp, dn, ino, new_key)

    def truncate(self, path: str, size: int) -> None:
        self.client.truncate(self.resolve(path), size)

    # =====================================================================
    # file handles
    # =====================================================================
    def open(self, path: str, mode: str = "r") -> int:
        """Modes: "r" read, "w" create/truncate, "a" append, "r+" read/write."""
        writable = any(m in mode for m in ("w", "a", "+"))
        created = False
        try:
            ino = self.resolve(path)
            if "w" in mode:
                self.client.truncate(ino, 0)
        except FSError as e:
            if e.errno != Errno.ENOENT or not writable or "r" == mode:
                raise
            parent, name = self.resolve_parent(path)
            bucket, key = self._cos_target(path)
            ino = self.client.create(parent, name, InodeKind.FILE,
                                     bucket, key or None)
            created = True
        attr = self.client.getattr(ino, cached_ok=False)  # close-to-open check
        if attr["kind"] == int(InodeKind.DIR):
            raise FSError(Errno.EISDIR, path)
        fh = next(self.client._fh)
        h = _Handle(fh=fh, ino=ino, path=path, writable=writable,
                    size_hint=0 if "w" in mode else attr["size"],
                    appending_new=created or "w" in mode)
        self.client.handles[fh] = h
        return fh

    def _h(self, fh: int) -> _Handle:
        h = self.client.handles.get(fh)
        if h is None:
            raise FSError(Errno.EINVAL, f"bad fh {fh}")
        return h

    # =====================================================================
    # read / write
    # =====================================================================
    def write(self, fh: int, off: int, data: bytes) -> int:
        h = self._h(fh)
        if not h.writable:
            raise FSError(Errno.EINVAL, "read-only handle")
        cl = self.client
        if cl.cfg.consistency == "strict":
            # read-after-write: reflect immediately in cluster-local cache
            seq = cl.next_seq()
            with span("fs.write.stage"):
                staged = cl.write_chunks(h.ino, off, data, seq)
            new_size = max(h.size_hint, off + len(data))
            with span("fs.write.commit"):
                cl.flush_write(h.ino, staged, new_size, seq)
            h.size_hint = new_size
            cl.invalidate_ino(h.ino)
        else:
            h.buffer.append((off, bytes(data)))
            h.buffered_bytes += len(data)
            h.size_hint = max(h.size_hint, off + len(data))
            if h.buffered_bytes >= cl.cfg.write_buffer_bytes:
                self._flush_buffer(h)
        return len(data)

    def append(self, fh: int, data: bytes) -> int:
        h = self._h(fh)
        return self.write(fh, h.size_hint, data)

    def _flush_buffer(self, h: _Handle) -> None:
        if not h.buffer:
            return
        cl = self.client
        # coalesce *consecutive* adjacent writes into runs (§6.2 batching);
        # temporal order must be preserved — later writes win on overlap,
        # so no reordering beyond merging a write that exactly extends the
        # previous one
        runs: list[tuple[int, bytearray]] = []
        for off, data in h.buffer:
            if runs and runs[-1][0] + len(runs[-1][1]) == off:
                runs[-1][1].extend(data)
            else:
                runs.append((off, bytearray(data)))
        seq = cl.next_seq()
        staged_all: dict[int, list[str]] = {}
        with span("fs.write.stage"):
            for off, data in runs:
                for coff, ids in cl.write_chunks(h.ino, off, bytes(data),
                                                 seq):
                    staged_all.setdefault(coff, []).extend(ids)
                seq = cl.next_seq()
        with span("fs.write.commit"):
            cl.flush_write(h.ino, sorted(staged_all.items()), h.size_hint,
                           seq)
        h.buffer.clear()
        h.buffered_bytes = 0
        cl.invalidate_ino(h.ino)

    def read(self, fh: int, off: int, length: int) -> bytes:
        h = self._h(fh)
        cl = self.client
        if cl.cfg.consistency == "strict":
            meta = cl.getattr(h.ino, cached_ok=False)
        else:
            if h.buffer:
                self._flush_buffer(h)  # read-your-own-writes within a handle
            meta = cl.getattr(h.ino, cached_ok=True)
        with span("fs.read"):
            return cl.read_range(h.ino, off, length, meta, handle=h)

    def fsync(self, fh: int) -> None:
        h = self._h(fh)
        self._flush_buffer(h)
        self.client.fsync_ino(h.ino)

    def close(self, fh: int) -> None:
        h = self.client.handles.pop(fh, None)
        if h is None:
            return
        if h.buffer:
            self.client.handles[fh] = h  # restore for flush path
            try:
                self._flush_buffer(h)
            finally:
                self.client.handles.pop(fh, None)
        h.stream_cache.clear()

    # =====================================================================
    # convenience
    # =====================================================================
    def write_file(self, path: str, data: bytes) -> None:
        fh = self.open(path, "w")
        try:
            self.write(fh, 0, data)
        finally:
            self.close(fh)

    def read_file_range(self, path: str, off: int, length: int) -> bytes:
        """Positioned whole-range read: open, read exactly [off, off+length)
        (short only at EOF), close.  The block-granular read path — callers
        with a segment table (e.g. `serving/kvstore.py` fetching one layer's
        KV block) pay only for the bytes they name, while the client's
        chunk-granular readahead still batches adjacent segments."""
        fh = self.open(path, "r")
        try:
            out = bytearray()
            pos, end = off, off + length
            while pos < end:
                blk = self.read(fh, pos, min(1 << 22, end - pos))
                if not blk:
                    break
                out += blk
                pos += len(blk)
            return bytes(out)
        finally:
            self.close(fh)

    def read_file(self, path: str) -> bytes:
        fh = self.open(path, "r")
        try:
            size = self.client.getattr(self._h(fh).ino,
                                       cached_ok=True)["size"]
            out = bytearray()
            pos = 0
            while pos < size:
                blk = self.read(fh, pos, min(1 << 22, size - pos))
                if not blk:
                    break
                out += blk
                pos += len(blk)
            return bytes(out)
        finally:
            self.close(fh)

    def walk_files(self, path: str = "/") -> list[str]:
        out: list[str] = []
        stack = [path.rstrip("/") or "/"]
        while stack:
            cur = stack.pop()
            for name in self.listdir(cur):
                child = (cur.rstrip("/") + "/" + name)
                st = self.stat(child)
                if st["kind"] == int(InodeKind.DIR):
                    stack.append(child)
                else:
                    out.append(child)
        return sorted(out)
