"""The unified descriptor-based client API (paper §5.5, session form).

The real FanStore detours glibc so unmodified binaries see one POSIX
surface. Our Python-idiomatic equivalent grew three overlapping entry
points instead (``FanStoreFS`` file objects, raw ``FanStoreCluster``
methods, ``PrefetchLoader`` plumbing). :class:`FanStoreSession` is the one
surface they all route through now: a per-process file-descriptor table
with ``open/pread/pwrite/fsync/close/opendir`` semantics over the layered
engine, plus the batched verbs (``read_many``/``write_many``/
``prefetch_window``) that make the engine fast.

Consistency surface (paper §3.5): multi-read / single-write. Reads
materialize the whole decompressed payload at ``open`` (so ``pread``/
``lseek`` are RAM operations); writes are append-only, streamed to the
placement owner by ``fsync`` (the write lane), and become visible on
``close``.

:class:`CheckpointWriter` rides on the session: it chunks checkpoint
shards through ``write``/``fsync`` so each chunk's fabric shipment (on the
concurrent ``NodeClock.write_s`` lane) overlaps both the production of the
next chunk and any active prefetch window — epoch makespan models
``max(consume, serve, prefetch, write)`` instead of write-then-prefetch
serialization.

Old names remain as deprecation shims: ``FanStoreFS``/``FanStoreFile``
(:mod:`repro.fanstore.fs`) are thin adapters over a session, and
``FanStoreCluster.write_file`` is the per-file serialized writer.
"""
from __future__ import annotations

import io
import json
import os
import threading
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.metadata import StatRecord
from repro.fanstore.spec import WorkerContext

__all__ = ["MOUNT", "FD_BASE", "FanStoreSession", "FanStoreDirEntry",
           "CheckpointWriter", "WorkerContext"]

MOUNT = "/fanstore"

# session fds start far above any real OS fd so the interception layer can
# route os.read/os.write/os.close by value without a table lookup race
FD_BASE = 1 << 20

_WRITE_FLAGS = os.O_WRONLY | os.O_RDWR


@dataclass
class _OpenFile:
    """One descriptor-table entry."""
    path: str                     # store-relative (mount stripped)
    writing: bool
    lane: str                     # "write" (concurrent) or "consume" (legacy)
    pos: int = 0
    data: Optional[bytes] = None  # read mode: whole materialized payload


class FanStoreDirEntry:
    """``os.DirEntry``-shaped result of :meth:`FanStoreSession.scandir`."""

    __slots__ = ("name", "path", "_st")

    def __init__(self, name: str, path: str, st: StatRecord):
        self.name = name
        self.path = path
        self._st = st

    def is_dir(self, *, follow_symlinks: bool = True) -> bool:
        return self._st.is_dir

    def is_file(self, *, follow_symlinks: bool = True) -> bool:
        return not self._st.is_dir

    def is_symlink(self) -> bool:
        return False

    def stat(self, *, follow_symlinks: bool = True) -> StatRecord:
        return self._st

    def inode(self) -> int:
        return self._st.st_ino

    def __fspath__(self) -> str:
        return self.path

    def __repr__(self) -> str:
        return f"<FanStoreDirEntry {self.name!r}>"


class _ScandirIterator:
    """Context-manager iterator, so ``os.walk`` over an intercepted mount
    works unmodified."""

    def __init__(self, entries: List[FanStoreDirEntry]):
        self._it = iter(entries)

    def __iter__(self) -> Iterator[FanStoreDirEntry]:
        return self._it

    def __next__(self) -> FanStoreDirEntry:
        return next(self._it)

    def __enter__(self) -> "_ScandirIterator":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def close(self) -> None:
        pass


class FanStoreSession:
    """The per-process client: node-local descriptor table over the engine.

    Every consumer goes through one of these — the POSIX-style adapters
    (``FanStoreFS``, interception), the data pipeline, checkpointing, the
    examples, and the benchmarks — instead of picking among layers.

    Paths may be given mount-prefixed (``/fanstore/train/x.bin``) or
    store-relative (``train/x.bin``); both resolve to the same file.

    Sessions are bound to a :class:`~repro.fanstore.spec.WorkerContext`
    (node + worker coordinates in the declared topology) — prefer
    ``cluster.connect(node_id, worker_id)`` over constructing directly.
    Co-located sessions (same node, different worker) share that node's
    cache tier; each read is attributed to its worker.

    ``lane`` picks the writer-side timeline for fd writes: ``"write"``
    (default) is the concurrent lane that overlaps demand reads and
    prefetch; ``"consume"`` reproduces the legacy serialized
    ``write_file`` accounting (the FS shim uses it).
    """

    def __init__(self, cluster: FanStoreCluster, node_id: int, *,
                 worker_id: int = 0, mount: str = MOUNT,
                 lane: str = "write", read_lane: str = "consume",
                 tenant: Optional[str] = None,
                 job: Optional[str] = None):
        self.cluster = cluster
        self.context = WorkerContext(node_id, worker_id)
        # direct construction must reject out-of-range coordinates just
        # like cluster.connect() — otherwise a bad worker_id fails late
        # (first cached read) or silently (cache disabled)
        declared = getattr(cluster, "workers_per_node", None)
        if declared is not None and worker_id >= declared:
            raise ValueError(
                f"worker_id {worker_id} outside workers_per_node="
                f"{declared} (declare more workers in the ClusterSpec)")
        self.node_id = node_id
        self.worker_id = worker_id
        self.mount = mount.rstrip("/")
        self.lane = lane
        # tenant-aware read routing (the serving plane): read_lane
        # "serve_app" books every read onto the concurrent serving
        # timeline attributed to `tenant` — cluster.connect(node, worker,
        # read_lane="serve_app", tenant="t-003") is how ServeGroup opens
        # its tenant sessions
        self.read_lane = read_lane
        self.tenant = tenant
        # multi-job seam: several jobs (train + eval) attach to one
        # namespace and share the node's cache tier; every read this
        # session issues is attributed to `job` on the tier ledger and
        # the NodeClock — cluster.connect(node, worker, job="eval") is
        # how the second job opens its sessions
        self.job = job
        self._fds: Dict[int, _OpenFile] = {}
        self._next_fd = FD_BASE
        self._lock = threading.Lock()

    # ---- path handling -----------------------------------------------------
    def resolve(self, path: str) -> str:
        """Strip the mount prefix; accept store-relative paths as-is."""
        path = os.fspath(path)
        if path == self.mount or path.startswith(self.mount + "/"):
            return path[len(self.mount):].strip("/")
        if path.startswith("/"):
            raise FileNotFoundError(
                f"{path}: outside FanStore mount {self.mount}")
        return path.strip("/")

    def owns(self, path: str) -> bool:
        path = os.fspath(path)
        return path == self.mount or path.startswith(self.mount + "/")

    # ---- descriptor table --------------------------------------------------
    def _alloc(self, entry: _OpenFile) -> int:
        with self._lock:
            fd = self._next_fd
            self._next_fd += 1
            self._fds[fd] = entry
        return fd

    def _entry(self, fd: int) -> _OpenFile:
        entry = self._fds.get(fd)
        if entry is None:
            raise OSError(9, "Bad file descriptor", str(fd))
        return entry

    def owns_fd(self, fd: int) -> bool:
        return fd in self._fds

    @property
    def open_fds(self) -> int:
        return len(self._fds)

    # ---- open/close --------------------------------------------------------
    @staticmethod
    def _writing_from(mode_or_flags: Union[str, int]) -> bool:
        if isinstance(mode_or_flags, int):
            return bool(mode_or_flags & _WRITE_FLAGS)
        mode = mode_or_flags.replace("b", "")
        if mode in ("r", "r+"):
            return False
        if mode in ("w", "x", "a", "w+", "x+"):
            return True
        raise ValueError(f"unsupported mode {mode_or_flags!r}")

    def open(self, path: str, mode_or_flags: Union[str, int] = "rb") -> int:
        """POSIX-style open: returns an integer descriptor. Accepts either a
        stdlib mode string (``"rb"``/``"wb"``/...) or ``os.O_*`` flags (the
        fd-level interception path)."""
        rel = self.resolve(path)
        if self._writing_from(mode_or_flags):
            self.cluster.write_begin(self.node_id, rel)
            return self._alloc(_OpenFile(rel, True, self.lane))
        data = self.cluster.read(self.node_id, rel,
                                 worker_id=self.worker_id,
                                 lane=self.read_lane, tenant=self.tenant,
                                 job=self.job)
        return self._alloc(_OpenFile(rel, False, self.lane, data=data))

    def close(self, fd: int) -> Optional[StatRecord]:
        """Close a descriptor. Closing a write fd commits it: the remaining
        buffer ships to the placement owner and the file becomes globally
        visible (returns its published stat)."""
        entry = self._entry(fd)
        try:
            if entry.writing:
                return self.cluster.commit_write(self.node_id, entry.path,
                                                 lane=entry.lane)
            return None
        finally:
            del self._fds[fd]

    def abort(self, fd: int) -> None:
        """Discard a descriptor without committing: an open write's
        buffered AND already-fsync'd (owner-staged) bytes are dropped, so
        a later writer of the same path starts clean."""
        entry = self._entry(fd)
        try:
            if entry.writing:
                self.cluster.abort_write(self.node_id, entry.path)
        finally:
            del self._fds[fd]

    # ---- reads -------------------------------------------------------------
    def pread(self, fd: int, count: int = -1,
              offset: Optional[int] = None) -> bytes:
        """Positional read; ``offset=None`` reads at (and advances) the
        cursor, an explicit offset leaves the cursor alone."""
        entry = self._entry(fd)
        if entry.writing or entry.data is None:
            raise io.UnsupportedOperation("not open for reading")
        at = entry.pos if offset is None else offset
        if count is None or count < 0:
            out = entry.data[at:]
        else:
            out = entry.data[at: at + count]
        if offset is None:
            entry.pos = at + len(out)
        return out

    def read(self, fd: int, count: int = -1) -> bytes:
        return self.pread(fd, count)

    # ---- writes ------------------------------------------------------------
    def pwrite(self, fd: int, data: bytes,
               offset: Optional[int] = None) -> int:
        """Append-only positional write: the effective offset (explicit, or
        the fd cursor — which an ``lseek`` may have moved) must equal the
        bytes written so far (outputs are write-once streams, §3.5).
        Seek-back-and-overwrite errors instead of silently appending."""
        entry = self._entry(fd)
        if not entry.writing:
            raise io.UnsupportedOperation("not open for writing")
        written = self.cluster.nodes[self.node_id].write_size(entry.path)
        at = entry.pos if offset is None else offset
        if at != written:
            raise io.UnsupportedOperation(
                f"{entry.path}: FanStore outputs are append-only "
                f"(offset {at} != size {written})")
        n = self.cluster.write_append(self.node_id, entry.path, data)
        entry.pos = written + n
        return n

    def write(self, fd: int, data: bytes) -> int:
        return self.pwrite(fd, data)

    def fsync(self, fd: int) -> int:
        """Flush a write fd's buffered bytes to the placement owner (the
        streaming half of the write path; metadata still publishes on
        close). No-op on read fds. Returns bytes shipped."""
        entry = self._entry(fd)
        if not entry.writing:
            return 0
        return self.cluster.flush_write(self.node_id, entry.path,
                                        lane=entry.lane)

    # ---- cursor / stat -----------------------------------------------------
    def lseek(self, fd: int, offset: int, whence: int = os.SEEK_SET) -> int:
        entry = self._entry(fd)
        if whence not in (os.SEEK_SET, os.SEEK_CUR, os.SEEK_END):
            raise ValueError(f"invalid whence {whence!r}")
        if entry.writing and whence == os.SEEK_END:
            raise io.UnsupportedOperation(
                "SEEK_END on an open write (size is undefined until close)")
        base = {os.SEEK_SET: 0, os.SEEK_CUR: entry.pos,
                os.SEEK_END: len(entry.data or b"")}[whence]
        entry.pos = max(0, base + offset)
        return entry.pos

    def fstat(self, fd: int) -> StatRecord:
        entry = self._entry(fd)
        if entry.writing:
            size = self.cluster.nodes[self.node_id].write_size(entry.path)
            return StatRecord.for_data(size)
        return StatRecord.for_data(len(entry.data or b""))

    # ---- namespace ops -----------------------------------------------------
    def stat(self, path: str) -> StatRecord:
        return self.cluster.stat(self.resolve(path))

    def exists(self, path: str) -> bool:
        try:
            self.stat(path)
            return True
        except FileNotFoundError:
            return False

    def getsize(self, path: str) -> int:
        return self.stat(path).st_size

    def listdir(self, path: str = "") -> List[str]:
        return self.cluster.readdir(self.resolve(path) if path else "")

    def unlink(self, path: str) -> None:
        """Delete a committed output file (output GC): the owner-side
        payload and the replicated metadata record drop together, and the
        name becomes writable again. Inputs are immutable
        (``PermissionError``); missing paths raise ``FileNotFoundError``.
        ``os.unlink``/``os.remove`` detour here under ``intercept()``."""
        self.cluster.unlink(self.node_id, self.resolve(path))

    remove = unlink

    def scandir(self, path: str = "") -> _ScandirIterator:
        """``os.scandir`` equivalent: entries carry name, joined path, and
        a ready stat (the paper's preprocessed metadata hash table — no
        per-entry round trips)."""
        raw = os.fspath(path) if path else self.mount
        rel = self.resolve(raw) if path else ""
        entries = []
        for name in self.cluster.readdir(rel):
            child = f"{rel}/{name}" if rel else name
            entries.append(FanStoreDirEntry(
                name, f"{raw.rstrip('/')}/{name}", self.cluster.stat(child)))
        return _ScandirIterator(entries)

    opendir = scandir

    def walk_count(self, path: str = "") -> int:
        """The start-of-training metadata traversal (paper §3.3): count
        files — committed outputs included, across both namespaces."""
        rel = self.resolve(path) if path else ""
        todo = [rel]
        n = 0
        while todo:
            d = todo.pop()
            for name in self.cluster.readdir(d):
                child = f"{d}/{name}" if d else name
                if self.cluster.is_dir(child):
                    todo.append(child)
                else:
                    n += 1
        return n

    # ---- batched verbs (the engine's fast path) ----------------------------
    def read_many(self, paths: Sequence[str], *,
                  materialize: bool = True) -> List[bytes]:
        """Batched whole-file reads: one modeled round trip per (this node,
        owner) pair instead of one per file. A serving session
        (``read_lane="serve_app"``) books the cost onto the concurrent
        serving timeline, attributed to its tenant.

        Returns read-only bytes-like objects: ``bytes``, or a
        ``memoryview`` over storage that FanStore never mutates, valid for
        as long as the caller holds it (see
        :meth:`FanStoreCluster.read_many` for which reads give views). The
        descriptor verbs (``open``/``read``/``pread``) always give
        ``bytes``."""
        return self.cluster.read_many(
            self.node_id, [self.resolve(p) for p in paths],
            worker_id=self.worker_id, materialize=materialize,
            lane=self.read_lane, tenant=self.tenant, job=self.job)

    def read_many_async(self, paths: Sequence[str], *,
                        materialize: bool = True) -> "Future[List[bytes]]":
        return self.cluster.read_many_async(
            self.node_id, [self.resolve(p) for p in paths],
            worker_id=self.worker_id, materialize=materialize,
            lane=self.read_lane, tenant=self.tenant, job=self.job)

    def write_many(self, entries: Sequence[Tuple[str, bytes]], *,
                   batched: bool = True) -> List[StatRecord]:
        """Batched writes: all payloads for one placement owner ride one
        round trip on the concurrent write lane."""
        return self.cluster.write_many(
            self.node_id, [(self.resolve(p), d) for p, d in entries],
            batched=batched, lane=self.lane)

    def write_many_async(self, entries: Sequence[Tuple[str, bytes]], *,
                         batched: bool = True) -> "Future[List[StatRecord]]":
        return self.cluster.write_many_async(
            self.node_id, [(self.resolve(p), d) for p, d in entries],
            batched=batched, lane=self.lane)

    def prefetch_window(self, paths: Sequence[str], *,
                        materialize: bool = True) -> int:
        return self.cluster.prefetch_window(
            self.node_id, [self.resolve(p) for p in paths],
            worker_id=self.worker_id, materialize=materialize)

    def checkpoint_writer(self, **kw) -> "CheckpointWriter":
        return CheckpointWriter(self, **kw)

    def transport_stats(self) -> Dict[str, object]:
        """This node's measured wire ledger: per-stripe wall time / bytes
        plus the on-the-wire codec's raw-vs-sent byte counts (all zero on
        purely modeled backends — the modeled view lives on the clocks)."""
        w = self.cluster.accounting.wall[self.node_id]
        return {
            "backend": self.cluster.backend,
            "stripes": dict(w.stripe_bytes),
            "stripe_ns": dict(w.stripe_ns),
            "wire_raw_bytes": w.wire_raw_bytes,
            "wire_sent_bytes": w.wire_sent_bytes,
            "wire_saved_bytes": w.wire_raw_bytes - w.wire_sent_bytes,
        }

    def metrics(self) -> Dict[str, object]:
        """This session's PER_RANK observability view (the metric
        counterpart of :meth:`transport_stats`): app-level series this
        (node, worker) rank recorded through ``cluster.metrics``, plus
        its node's modeled lanes and its own worker-attributed cache
        counters, all from one consistent accounting snapshot."""
        return self.cluster.metrics.rank_view(self.node_id, self.worker_id)

    def record_metric(self, name: str, value: float, **kw) -> None:
        """Record one observation on the cluster collector, attributed
        to this session's (node, worker) rank. Keyword arguments pass
        through to :meth:`repro.fanstore.metrics.MetricsCollector.
        record_metric` (``reduce=``, ``rate=``)."""
        self.cluster.metrics.record_metric(
            name, value, rank=(self.node_id, self.worker_id), **kw)

    def fault_stats(self) -> Dict[str, object]:
        """The cluster's fault ledger: injector counters (injected/
        dropped/errored/delayed, whether the kill trigger fired), the
        accounting retry total, and the current failed-node set. All
        counters are zero with no ``faults`` policy in the spec."""
        return self.cluster.fault_stats()

    # ---- lifecycle ---------------------------------------------------------
    def close_all(self) -> None:
        """Abort open writes (uncommitted data is discarded — visible-until-
        finish means nothing published, including owner-staged fsync'd
        chunks) and drop all descriptors. The cluster (and its transport
        backend) stays up: sessions are per-process views, many share one
        cluster — tear the wire itself down with ``cluster.close()``."""
        for fd in list(self._fds):
            self.abort(fd)

    def close_session(self) -> None:
        """Session teardown: drop every descriptor (open writes abort)."""
        self.close_all()

    def __enter__(self) -> "FanStoreSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close_all()


class CheckpointWriter:
    """Stream checkpoint shards through a session in fsync'd chunks.

    Each shard is one output file: ``write_shard`` opens it, writes
    ``chunk_bytes``-sized chunks, and fsyncs after each so the chunk's
    shipment to the placement owner rides the concurrent ``write_s`` lane
    while the next chunk is produced — and while any active prefetch
    window keeps fetching. Epoch makespan is then
    ``max(consume, serve, prefetch, write)`` per node rather than the
    serialized write-then-prefetch sum (pinned by tests/benchmarks).
    """

    def __init__(self, session: FanStoreSession, *,
                 chunk_bytes: int = 1 << 20):
        if chunk_bytes < 1:
            raise ValueError("chunk_bytes must be >= 1")
        self.session = session
        self.chunk_bytes = chunk_bytes
        self.shards_written = 0
        self.bytes_written = 0
        self.chunks_flushed = 0

    def write_shard(self, path: str, payload: bytes) -> StatRecord:
        """Stream one shard; visible (and immutable) once this returns."""
        fd = self.session.open(path, "wb")
        try:
            view = memoryview(payload)
            for off in range(0, max(len(view), 1), self.chunk_bytes):
                self.session.write(fd, bytes(view[off:off + self.chunk_bytes]))
                self.session.fsync(fd)
                self.chunks_flushed += 1
        except BaseException:
            self.session.abort(fd)       # drops buffered + staged chunks
            raise
        st = self.session.close(fd)
        self.shards_written += 1
        self.bytes_written += len(payload)
        return st

    def write_json(self, path: str, obj) -> StatRecord:
        """Serialize + stream a manifest; write it LAST — its visibility is
        the checkpoint's commit marker (mirrors the on-disk atomic rename)."""
        return self.write_shard(
            path, json.dumps(obj, sort_keys=True).encode())
