"""The shared-memory backend: the Hoard-style co-located fast path.

When requester and owner share an address space (workers co-located on
one physical node — this container, by construction, holds the whole
simulated cluster), shipping payloads through a socket pays framing,
syscalls, and two copies for bytes that are already reachable. This
backend takes the node-local tier's shortcut instead:

* ``_move_fetch`` asks the owner's ``NodeStore`` for **zero-copy
  ``memoryview``s** over its partition blobs (``serve_remote_view``) and
  materializes each payload with a single ``bytes()`` copy — no frames,
  no syscalls, no intermediate buffer. Uncompressed files never exist
  twice; compressed ones pay exactly the one decompression every backend
  pays.
* ``fetch_views`` exposes the views themselves for callers that can
  consume borrowed buffers (the benchmark's true zero-copy arm).
* ``_move_put`` stages output chunks directly into the owner's staging
  table (co-located writers share the store).

For co-located worker *processes* (separate interpreters on one node),
:class:`ShmArena` provides the same trick over
``multiprocessing.shared_memory``: ``export`` copies a payload once into
a named segment; any process that knows the (name, size) pair maps it
read-only with zero further copies. The backend exports committed
payloads on demand via :meth:`export_output`, or a whole manifest of
paths via :meth:`export_paths`. The spawn-side counterpart is
:func:`attach_and_digest`: a worker process rebuilds the owner's
topology from a ``ClusterSpec`` JSON string, attaches the exported
segments by (name, size) handle, and reads the payloads byte-identical
— the cross-process seam, closed by a real ``multiprocessing`` spawn
test. Arena support degrades gracefully (``ShmArena.available``) where
``/dev/shm`` is absent.

Measured wall time accrues exactly as on the socket backend (requester
lane + owner serve lane), so ``BENCH_io.json``'s ``measured`` block can
show the co-located path beating the socket path on the same trace —
the modeled clocks accrue identically to every other backend.
"""
from __future__ import annotations

import hashlib
import threading
import time
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from repro.fanstore.backends.base import TransportBackend
from repro.fanstore.wire import FetchItem

__all__ = ["SharedMemoryBackend", "ShmArena", "attach_and_digest"]

try:
    from multiprocessing import shared_memory as _shm
except ImportError:                     # pragma: no cover - stdlib on 3.8+
    _shm = None


class ShmArena:
    """Named ``multiprocessing.shared_memory`` segments for cross-process
    zero-copy: one export = one copy into the segment; every mapping
    after that is free. Owns its segments — ``close()`` unlinks them."""

    #: False when the platform offers no POSIX shared memory
    available = _shm is not None

    def __init__(self) -> None:
        # name -> (segment, owns): only segments THIS arena created get
        # unlinked at close; attached peer exports are merely unmapped
        self._segments: Dict[str, Tuple["_shm.SharedMemory", bool]] = {}
        self._lock = threading.Lock()

    def export(self, data: bytes) -> Tuple[str, int]:
        """Copy ``data`` into a fresh segment; returns (name, size) — the
        handle another process needs to map it."""
        if _shm is None:
            raise RuntimeError("multiprocessing.shared_memory unavailable")
        seg = _shm.SharedMemory(create=True, size=max(len(data), 1))
        seg.buf[:len(data)] = data
        with self._lock:
            self._segments[seg.name] = (seg, True)
        return seg.name, len(data)

    def view(self, name: str, size: int) -> memoryview:
        """Map a segment (local or exported by a peer) as a read view."""
        if _shm is None:
            raise RuntimeError("multiprocessing.shared_memory unavailable")
        with self._lock:
            hit = self._segments.get(name)
        if hit is None:                # exported by another arena: attach
            seg = _shm.SharedMemory(name=name)
            with self._lock:
                hit = self._segments.setdefault(name, (seg, False))
            if hit[0] is not seg:      # lost the insert race: drop ours
                seg.close()
        return hit[0].buf[:size]

    def drop(self, name: str) -> None:
        """Release ONE segment early (e.g. an unlinked output's
        registration): unmap, and unlink if this arena created it."""
        with self._lock:
            hit = self._segments.pop(name, None)
        if hit is None:
            return
        seg, owns = hit
        try:
            seg.close()
        except BufferError:            # a borrowed view is still live
            pass
        if owns:
            try:
                seg.unlink()
            except FileNotFoundError:
                pass

    def close(self) -> None:
        with self._lock:
            segments = list(self._segments.values())
            self._segments.clear()
        for seg, owns in segments:
            try:
                seg.close()
            except BufferError:
                # a consumer still holds a borrowed view; carry on — the
                # memory is freed when the last map drops
                pass
            if owns:                   # never unlink a peer's live export
                try:
                    seg.unlink()
                except FileNotFoundError:  # owner gone and name reclaimed
                    pass

    def __len__(self) -> int:
        return len(self._segments)


class SharedMemoryBackend(TransportBackend):
    """Zero-copy co-located transfers over the owner's own buffers."""

    name = "shm"
    measured = True

    def __init__(self, net, nodes, clocks, *, wall=None,
                 num_threads: int = 8, arena: Optional[ShmArena] = None,
                 **wire_opts):
        super().__init__(net, nodes, clocks, wall=wall,
                         num_threads=num_threads, **wire_opts)
        self.arena = arena

    def _stop_serving(self) -> None:
        if self.arena is not None:
            self.arena.close()

    # ---- movement primitives -----------------------------------------------
    @staticmethod
    def _materialize(view: memoryview) -> bytes:
        """Owning bytes for a served view with the fewest copies: a view
        spanning a whole bytes object (freshly decompressed payloads,
        committed outputs) is that object — hand it back uncopied; only
        borrowed partition-blob slices pay the one materializing copy."""
        obj = view.obj
        if type(obj) is bytes and view.nbytes == len(obj):
            return obj
        return bytes(view)

    def _move_fetch(self, requester: int, owner: int,
                    items: Sequence[FetchItem], materialize: bool,
                    verb: str, views: bool = False
                    ) -> Tuple[List[bytes], int]:
        if not materialize:
            return [b"" for _ in items], 0
        store = self.nodes[owner]
        t0 = time.perf_counter_ns()
        out = [self._materialize(store.serve_remote_view(it.path))
               for it in items]
        # co-located: the owner's "serving" IS the view construction; the
        # copy happens on the requester's side of the same duration
        return out, time.perf_counter_ns() - t0

    def _move_put(self, writer: int, owner: int,
                  pairs: Sequence[Tuple[FetchItem, bytes]]) -> int:
        store = self.nodes[owner]
        t0 = time.perf_counter_ns()
        for item, data in pairs:
            store.stage_output(writer, item.path, data)
        return time.perf_counter_ns() - t0

    # ---- zero-copy extras --------------------------------------------------
    def fetch_views(self, requester: int, owner: int,
                    items: Sequence[FetchItem]) -> List[memoryview]:
        """Borrowed zero-copy views of the owner's payloads (no modeled
        accounting: this is the raw fast path for callers that manage
        their own lifetimes, e.g. the measured benchmark)."""
        store = self.nodes[owner]
        t0 = time.perf_counter_ns()
        views = [store.serve_remote_view(it.path) for it in items]
        dt = time.perf_counter_ns() - t0
        self._wall_accrue(requester, "consume", dt,
                          bytes_in=sum(v.nbytes for v in views), requests=1,
                          owner=owner, serve_ns=dt,
                          bytes_out=sum(v.nbytes for v in views))
        return views

    def export_output(self, owner: int, path: str) -> Tuple[str, int]:
        """Copy a committed output payload into a shared-memory segment so
        a co-located worker *process* can map it zero-copy; returns the
        (segment name, size) handle. Requires an :class:`ShmArena`."""
        if self.arena is None:
            raise RuntimeError("SharedMemoryBackend built without an arena")
        data = self._materialize(self.nodes[owner].serve_remote_view(path))
        return self.arena.export(data)

    def export_paths(self, owner: int, paths: Sequence[str]
                     ) -> Dict[str, Tuple[str, int]]:
        """Export a manifest of payloads (inputs OR committed outputs the
        ``owner`` node holds) into shared-memory segments: the
        ``{path: (segment name, size)}`` handle table a spawned worker
        process needs — ship it beside ``cluster.spec.to_json()`` and the
        worker reconstructs the topology and maps every payload with
        :func:`attach_and_digest` (or :meth:`ShmArena.view` directly)."""
        if self.arena is None:
            raise RuntimeError("SharedMemoryBackend built without an arena")
        store = self.nodes[owner]
        return {p: self.arena.export(
                    self._materialize(store.serve_remote_view(p)))
                for p in paths}


def attach_and_digest(spec_json: str,
                      handles: Mapping[str, Tuple[str, int]]
                      ) -> Dict[str, object]:
    """Worker-process entry point for the cross-process shm seam.

    Runs in a SPAWNED interpreter (module-level so ``multiprocessing``'s
    spawn context can import it): rebuilds the owner's topology from the
    serialized :class:`~repro.fanstore.spec.ClusterSpec`, attaches every
    exported segment by its (name, size) handle, and returns
    ``{"spec_json": <re-serialized spec>, "digests": {path: sha256hex},
    "sizes": {path: nbytes}}`` — the parent asserts the spec round-trip
    is identity and the digests match its own payloads byte-for-byte.
    The attached segments are unmapped (never unlinked: this arena did
    not create them) before returning.
    """
    # local import: repro.fanstore.spec imports this module's package
    from repro.fanstore.spec import ClusterSpec
    spec = ClusterSpec.from_json(spec_json)     # validates the topology
    arena = ShmArena()
    digests: Dict[str, str] = {}
    sizes: Dict[str, int] = {}
    try:
        for path, (name, size) in handles.items():
            view = arena.view(name, size)
            try:
                digests[path] = hashlib.sha256(view).hexdigest()
                sizes[path] = view.nbytes
            finally:
                view.release()          # drop the borrow before unmapping
    finally:
        arena.close()                   # attached-only: unmaps, no unlink
    return {"spec_json": spec.to_json(), "digests": digests,
            "sizes": sizes,
            "workers_per_node": spec.workers_per_node}
