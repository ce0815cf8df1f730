"""Per-node FanStore store (paper §5.4).

Each compute node runs one ``NodeStore`` holding:
  * the partitions assigned to it ("local SSD" tier — kept in RAM here, with
    an optional spill directory to model the on-disk layout),
  * an index path -> (partition_id, record) for its local files, and a
    read record per file resolved when its partition is indexed (the
    cost model's sizes; for a payload stored raw, its bounds in the blob),
  * the refcount file cache: a file's decompressed bytes stay cached while any
    open descriptor refers to it and are evicted when the count reaches zero
    (paper: uniform random access defeats LRU; evict-on-last-close instead),
  * write buffers for output files: bytes are concatenated in RAM and the
    metadata becomes visible only when ``close()`` forwards it to the node
    chosen by the placement hash (visible-until-finish consistency). The
    write lane may stream chunks ahead of close (``write_take``); the
    placement owner stages them per (writer, path) and joins them at commit,
  * the output tier: committed payloads for files this node owns as the
    placement target — outputs are served like any other local file
    (``open_local``/``serve_remote`` fall back to it).
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from repro.fanstore.layout import FileRecord, iter_partition
from repro.fanstore.metadata import StatRecord
from repro.fanstore.wire import FetchItem


@dataclass
class _CacheEntry:
    data: bytes
    refcount: int = 0


class ReadRecord(NamedTuple):
    """What a read of one input file needs, resolved once when its
    partition is indexed (an input is immutable while it is held, paper
    §3.5): the cost model's sizes, and for a payload stored raw a
    read-only view of the whole blob (its ``obj`` the blob) and the
    payload's bounds in it (``blob`` None where it must decompress)."""
    item: FetchItem
    blob: Optional[memoryview]
    start: int
    stop: int


@dataclass
class _WriteBuffer:
    chunks: List[bytes] = field(default_factory=list)
    flushed: int = 0        # bytes already streamed to the placement owner
    buffered: int = 0       # bytes in chunks (kept so size checks are O(1))

    def append(self, data: bytes) -> int:
        self.chunks.append(bytes(data))
        self.buffered += len(data)
        return len(data)

    def take(self) -> bytes:
        """Drain buffered-but-unflushed bytes (streaming fsync)."""
        data = b"".join(self.chunks)
        self.chunks.clear()
        self.flushed += len(data)
        self.buffered = 0
        return data

    def getvalue(self) -> bytes:
        return b"".join(self.chunks)


class NodeStore:
    """One node's slice of the transient store."""

    def __init__(self, node_id: int, *, codec: str = "lzss",
                 spill_dir: Optional[str] = None) -> None:
        self.node_id = node_id
        self.codec = codec
        self.spill_dir = spill_dir
        self._partitions: Dict[int, bytes] = {}
        self._index: Dict[str, Tuple[int, FileRecord]] = {}
        self._reads: Dict[str, ReadRecord] = {}
        self._cache: Dict[str, _CacheEntry] = {}
        # the refcount cache is mutated by every thread that serves this
        # node — transport pool workers AND (socket backend) per-connection
        # handler threads — so open/release are locked: an unlocked
        # refcount ++/-- pair can double-delete an entry (spurious
        # KeyError to an innocent client) or strand it forever
        self._cache_lock = threading.Lock()
        self._writes: Dict[str, _WriteBuffer] = {}
        # output tier (this node as the placement owner of written files):
        # committed payloads plus per-(writer, path) staging for chunks
        # streamed ahead of close() by the write lane
        self._outputs: Dict[str, bytes] = {}
        self._staging: Dict[Tuple[int, str], List[bytes]] = {}
        # counters for benchmarks / tests
        self.stats = {"local_opens": 0, "cache_hits": 0, "evictions": 0,
                      "bytes_read": 0, "bytes_served": 0, "decompressed": 0}

    # ---- partition loading -------------------------------------------------
    def load_partition(self, partition_id: int, blob: bytes) -> List[str]:
        """Install a partition; returns the paths it contributes."""
        if self.spill_dir is not None:
            os.makedirs(self.spill_dir, exist_ok=True)
            fn = os.path.join(self.spill_dir, f"part_{partition_id:06d}.fst")
            with open(fn, "wb") as f:
                f.write(blob)
        self._partitions[partition_id] = blob
        view = memoryview(blob).toreadonly()
        paths = []
        for rec in iter_partition(blob, codec=self.codec):
            self._index[rec.path] = (partition_id, rec)
            stop = rec.data_offset + rec.stored_size
            self._reads[rec.path] = ReadRecord(
                FetchItem(path=rec.path, size=rec.stat.st_size,
                          stored=rec.stored_size,
                          compressed=bool(rec.compressed_size)),
                None if rec.compressed_size else view, rec.data_offset, stop)
            paths.append(rec.path)
        return paths

    def drop_partition(self, partition_id: int) -> None:
        self._partitions.pop(partition_id, None)
        self._index = {p: (pid, r) for p, (pid, r) in self._index.items()
                       if pid != partition_id}
        self._reads = {p: self._reads[p] for p in self._index}

    @property
    def partition_ids(self) -> Tuple[int, ...]:
        return tuple(sorted(self._partitions))

    def has(self, path: str) -> bool:
        return path in self._index

    def local_paths(self) -> List[str]:
        return list(self._index)

    def read_record(self, path: str) -> Optional[ReadRecord]:
        return self._reads.get(path)

    def count_raw(self, paths: Iterable[str]) -> int:
        """How many of ``paths`` this store holds as input records stored
        raw: the files :meth:`gather` serves straight from a blob."""
        reads = self._reads
        return sum(1 for p in paths
                   if (rec := reads.get(p)) is not None
                   and rec.blob is not None)

    def record_for(self, path: str) -> Optional[FileRecord]:
        hit = self._index.get(path)
        return hit[1] if hit else None

    def locate(self, path: str) -> Optional[Tuple[int, FileRecord]]:
        """(partition_id, record) for a local input file — the coordinates
        a registration-based wire (RDMA) needs to pin the file's stored
        bytes at their offset inside the partition blob."""
        return self._index.get(path)

    def partition_blob(self, partition_id: int) -> bytes:
        """The raw partition image (registration targets map it whole:
        one pinned segment serves every record in the partition)."""
        return self._partitions[partition_id]

    # ---- reads (local tier) ------------------------------------------------
    def open_local(self, path: str) -> bytes:
        """Open+read a local file: refcount++ and return (cached) bytes.

        Falls back to the output tier (files this node owns as the
        placement target of committed writes); outputs are RAM-resident
        already, so they bypass the refcount cache.
        """
        with self._cache_lock:
            return self._open_locked(path)

    def _open_locked(self, path: str) -> bytes:
        entry = self._cache.get(path)
        if entry is not None:
            entry.refcount += 1
            self.stats["cache_hits"] += 1
            return entry.data
        hit = self._index.get(path)
        if hit is None:
            out = self._outputs.get(path)
            if out is not None:
                self.stats["local_opens"] += 1
                self.stats["bytes_read"] += len(out)
                return out
            raise FileNotFoundError(path)
        pid, rec = hit
        blob = self._partitions[pid]
        raw = blob[rec.data_offset: rec.data_offset + rec.stored_size]
        if rec.compressed_size:
            from repro.fanstore.layout import _decompress
            data = _decompress(self.codec, bytes(raw), rec.stat.st_size)
            self.stats["decompressed"] += 1
        else:
            data = bytes(raw)
        self._cache[path] = _CacheEntry(data=data, refcount=1)
        self.stats["local_opens"] += 1
        self.stats["bytes_read"] += len(data)
        return data

    def release(self, path: str) -> None:
        """close(): refcount--; evict at zero (paper's counter table)."""
        with self._cache_lock:
            self._release_locked(path)

    def _release_locked(self, path: str) -> None:
        entry = self._cache.get(path)
        if entry is None:
            return
        entry.refcount -= 1
        if entry.refcount <= 0:
            del self._cache[path]
            self.stats["evictions"] += 1

    def gather(self, paths: Sequence[str], *,
               views: bool = False) -> List[bytes]:
        """Read many files in one pass, as an ``open_local`` and a
        ``release`` of each would: the same bytes and the same
        ``stats``. An input record stored raw that no descriptor holds
        open is copied straight from its partition blob, without the
        insert into and delete from the refcount cache that the pair
        makes; every other file takes that pair.

        With ``views`` such a record is not copied: it comes back as a
        read-only ``memoryview`` over its partition blob (``obj`` the
        blob). A blob is never mutated, and the view holds it alive, so
        the view stays valid and equal to the file after the partition
        is dropped. Every other file is still owning ``bytes``."""
        reads, cache = self._reads, self._cache
        out: List[bytes] = []
        sliced = nbytes = 0
        with self._cache_lock:
            for path in paths:
                rec = reads.get(path)
                if rec is None or rec.blob is None or path in cache:
                    out.append(self._open_locked(path))
                    self._release_locked(path)
                    continue
                data = rec.blob[rec.start:rec.stop]
                if not views:
                    data = bytes(data)
                out.append(data)
                sliced += 1
                nbytes += len(data)
            stats = self.stats
            stats["local_opens"] += sliced
            stats["bytes_read"] += nbytes
            stats["evictions"] += sliced
        return out

    def serve_remote(self, path: str) -> bytes:
        """Handle a peer's round-trip read request (no cache interaction)."""
        data = self.open_local(path)
        # the serving side does not hold a descriptor; release immediately
        self.release(path)
        self.stats["bytes_served"] += len(data)
        return data

    def serve_many(self, paths: Sequence[str], *,
                   views: bool = False) -> List[bytes]:
        """A peer's batched read request: :meth:`gather` (``views`` as
        there), booked as served (the same ``stats`` as a
        ``serve_remote`` of each)."""
        out = self.gather(paths, views=views)
        self.stats["bytes_served"] += sum(map(len, out))
        return out

    def serve_remote_view(self, path: str) -> memoryview:
        """Zero-copy serve for co-located requesters (the shared-memory
        backend): a borrowed ``memoryview`` over this store's own buffers.

        Uncompressed partition records are served as a view straight into
        the partition blob — the payload never exists twice; committed
        outputs are viewed in place. Compressed records must decompress
        (every backend pays that) and the view covers the fresh buffer.
        The view is read-only borrowed memory: valid until the partition
        (or output) is dropped, never to be mutated.
        """
        out = self._outputs.get(path)
        if out is not None:
            self.stats["bytes_served"] += len(out)
            return memoryview(out)
        hit = self._index.get(path)
        if hit is None:
            raise FileNotFoundError(path)
        pid, rec = hit
        blob = self._partitions[pid]
        raw = memoryview(blob)[rec.data_offset:
                               rec.data_offset + rec.stored_size]
        if rec.compressed_size:
            from repro.fanstore.layout import _decompress
            data = _decompress(self.codec, bytes(raw), rec.stat.st_size)
            self.stats["decompressed"] += 1
            self.stats["bytes_served"] += len(data)
            return memoryview(data)
        self.stats["bytes_served"] += rec.stored_size
        return raw

    @property
    def cached_bytes(self) -> int:
        return sum(len(e.data) for e in self._cache.values())

    @property
    def open_files(self) -> int:
        return sum(e.refcount for e in self._cache.values())

    # ---- writes (output tier) ----------------------------------------------
    def write_begin(self, path: str) -> None:
        if path in self._index:
            raise PermissionError(f"{path}: input files are immutable (single-write)")
        self._writes.setdefault(path, _WriteBuffer())

    def write_append(self, path: str, data: bytes) -> int:
        buf = self._writes.get(path)
        if buf is None:
            raise IOError(f"{path}: not open for write")
        return buf.append(data)

    def write_take(self, path: str) -> bytes:
        """Drain the open write's unflushed bytes (streaming fsync); the
        write stays open and the drained bytes count toward the final stat."""
        buf = self._writes.get(path)
        if buf is None:
            raise IOError(f"{path}: not open for write")
        return buf.take()

    def write_size(self, path: str) -> int:
        """Bytes written so far (flushed + buffered) on an open write."""
        buf = self._writes.get(path)
        if buf is None:
            raise IOError(f"{path}: not open for write")
        return buf.flushed + buf.buffered

    def write_abort(self, path: str) -> None:
        self._writes.pop(path, None)

    def write_finish(self, path: str) -> Tuple[StatRecord, bytes]:
        """close() on a written file: final stat (all bytes, including any
        already streamed to the owner) + the remaining unflushed payload.

        The caller (cluster) ships the remainder to the placement owner and
        publishes the metadata; only then does the file become visible.
        """
        buf = self._writes.pop(path, None)
        if buf is None:
            raise IOError(f"{path}: not open for write")
        data = buf.getvalue()
        return StatRecord.for_data(buf.flushed + len(data)), data

    @property
    def pending_writes(self) -> int:
        return len(self._writes)

    # ---- output tier (this node as placement owner) ------------------------
    def stage_output(self, writer: int, path: str, chunk: bytes) -> None:
        """Receive one streamed chunk of an in-flight write. Staging is
        keyed by (writer, path) so two racing writers never interleave."""
        self._staging.setdefault((writer, path), []).append(chunk)

    def drop_staging(self, writer: int, path: str) -> None:
        self._staging.pop((writer, path), None)

    def commit_output(self, writer: int, path: str) -> bytes:
        """Join the writer's staged chunks into the committed payload."""
        data = b"".join(self._staging.pop((writer, path), []))
        self._outputs[path] = data
        return data

    def has_output(self, path: str) -> bool:
        return path in self._outputs

    def output_size(self, path: str) -> Optional[int]:
        """Size of a committed output payload WITHOUT booking a read
        (metadata-only callers, e.g. the wire STAT verb); None when this
        node does not own the path."""
        data = self._outputs.get(path)
        return len(data) if data is not None else None

    def drop_output(self, path: str) -> int:
        """Output GC: free a committed payload this node owns (unlink).
        Returns the bytes reclaimed (0 when the path was not held)."""
        data = self._outputs.pop(path, None)
        return len(data) if data is not None else 0

    @property
    def output_bytes(self) -> int:
        return sum(len(v) for v in self._outputs.values())
