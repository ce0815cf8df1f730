"""POSIX-style file-object interface over a FanStore cluster (paper §5.5).

DEPRECATED surface: ``FanStoreFS``/``FanStoreFile`` are kept as thin
adapters over the descriptor-based :class:`repro.fanstore.api.FanStoreSession`
so pre-session call sites keep working unchanged. New code should use the
session directly — it exposes the same namespace plus the fd-level verbs
(``pread``/``pwrite``/``fsync``/``opendir``) and the batched write path.

Semantics are unchanged: multi-read / single-write (§3.5), whole-payload
materialization at open so ``seek``/partial ``read`` are RAM operations,
writes visible on ``close()``. The FS adapter commits on the legacy
serialized ``consume`` lane, byte-for-byte the ``cluster.write_file``
accounting (regression-pinned).
"""
from __future__ import annotations

import io
import os
from typing import List

from repro.fanstore.api import MOUNT, FanStoreSession
from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.metadata import StatRecord

__all__ = ["MOUNT", "FanStoreFile", "FanStoreFS"]


class FanStoreFile(io.RawIOBase):
    """A read- or write-mode file object wrapping one session descriptor."""

    def __init__(self, session: FanStoreSession, path: str, mode: str):
        super().__init__()
        self._session = session
        self._path = path
        self._writing = session._writing_from(mode)
        self._fd = session.open(path, mode)

    @property
    def fd(self) -> int:
        return self._fd

    # -- reads --
    def readable(self) -> bool:
        return not self._writing

    def read(self, size: int = -1) -> bytes:
        if self._writing:
            raise io.UnsupportedOperation("not open for reading")
        return self._session.read(self._fd, -1 if size is None else size)

    def seekable(self) -> bool:
        return not self._writing

    def seek(self, offset: int, whence: int = os.SEEK_SET) -> int:
        # nonstandard whence -> ValueError; SEEK_END during a write ->
        # io.UnsupportedOperation (size is undefined until close) — the
        # session's lseek enforces both
        return self._session.lseek(self._fd, offset, whence)

    # -- writes --
    def writable(self) -> bool:
        return self._writing

    def write(self, data) -> int:
        if not self._writing:
            raise io.UnsupportedOperation("not open for writing")
        return self._session.write(self._fd, bytes(data))

    def flush(self) -> None:
        # file-object flush is a buffer no-op (bytes ship on close, the
        # legacy visible-on-close contract); use session.fsync for the
        # streaming write lane
        super().flush()

    def close(self) -> None:
        if self.closed:
            return
        try:
            if self._session.owns_fd(self._fd):
                self._session.close(self._fd)
        finally:
            super().close()


class FanStoreFS:
    """Deprecated per-process client adapter; see ``FanStoreSession``.

    The FS adapter pins the legacy behaviors: paths must be mount-prefixed,
    modes must be binary, and write commits account on the serialized
    demand lane exactly like ``cluster.write_file``.
    """

    def __init__(self, cluster: FanStoreCluster, node_id: int, *,
                 mount: str = MOUNT):
        self.session = FanStoreSession(cluster, node_id, mount=mount,
                                       lane="consume")
        self.cluster = cluster
        self.node_id = node_id
        self.mount = self.session.mount

    def resolve(self, path: str) -> str:
        """Strip the mount prefix; reject paths outside the mount."""
        path = os.fspath(path)
        if not path.startswith(self.mount + "/") and path != self.mount:
            raise FileNotFoundError(
                f"{path}: outside FanStore mount {self.mount}")
        return self.session.resolve(path)

    def owns(self, path: str) -> bool:
        return self.session.owns(path)

    def open(self, path: str, mode: str = "rb") -> FanStoreFile:
        if "b" not in mode:
            raise ValueError("FanStore is a binary store; use 'rb'/'wb'")
        self.resolve(path)                     # enforce mount-prefixed paths
        return FanStoreFile(self.session, path, mode)

    def read_many(self, paths: List[str]) -> List[bytes]:
        """Batched whole-file reads through the engine: one modeled round
        trip per (this node, owner) pair instead of one per file. Read-only
        bytes-like results, as :meth:`FanStoreSession.read_many` gives;
        a file object's ``read`` gives ``bytes``."""
        return self.session.read_many([self.resolve(p) for p in paths])

    def stat(self, path: str) -> StatRecord:
        return self.session.stat(self.resolve(path))

    def listdir(self, path: str) -> List[str]:
        self.resolve(path)                     # reject paths outside the mount
        return self.session.listdir(path)

    def scandir(self, path: str):
        self.resolve(path)
        return self.session.scandir(path)

    def exists(self, path: str) -> bool:
        try:
            self.stat(path)
            return True
        except FileNotFoundError:
            return False

    def unlink(self, path: str) -> None:
        """Delete a committed output file (output GC; inputs are
        immutable). Mount-prefixed path, like every FS-adapter call."""
        self.resolve(path)
        self.session.unlink(path)

    def walk_count(self, path: str = "") -> int:
        """The start-of-training metadata traversal (paper §3.3): count files."""
        return self.session.walk_count(path)
