"""The RDMA-class backend: one-sided reads over registered memory.

The paper's remote path is two-sided — every fetch costs the owner a
request handling plus a scatter-gather on its serving thread. An
RDMA-style fabric removes the owner's CPU from the data path entirely:
the owner *registers* (pins) memory regions up front and publishes a
registration table; a requester that holds a region's (segment, offset,
rkey) coordinates reads the bytes with a one-sided verb the owner never
sees. This backend models that contract faithfully for co-located
processes:

* **Registration table** — per owner, ``path -> _Region``: the shared
  segment holding the bytes, the offset/length inside it, an rkey-style
  protection token, and the codec coordinates (stored bytes may be
  LZSS-compressed in the partition image; the REQUESTER decompresses,
  exactly as a real one-sided read hands back raw registered bytes).
  Input partitions are registered whole — one pinned segment per
  partition blob serves every record in it at its ``data_offset`` —
  and committed outputs are registered per path on first read.
  Registration happens lazily on first touch (the control path, amortized
  once per partition/output); :meth:`registration_table` exposes an
  owner's published table.
* **One-sided read** — :meth:`_move_fetch` looks up the region, verifies
  the token (a mismatched rkey raises ``PermissionError``, the fabric's
  protection-domain check), and copies the bytes out of the registered
  segment. It reports ``serve_ns = 0`` ALWAYS: the owner's measured serve
  lane never accrues, because its CPU never ran — the no-serve-lane
  contract the cross-backend tests pin.
* **Measured arm** — registered segments are real
  ``multiprocessing.shared_memory`` segments (:class:`ShmArena`), so
  co-located worker processes can attach and read with zero owner
  involvement; where ``/dev/shm`` is unavailable the regions degrade to
  in-process buffer views with identical semantics.
* **Modeled accounting** — this is the one backend whose fabric genuinely
  differs, so it overrides the two accounting seams: a remote read costs
  the requester ``trips * rdma_lookup_s + stored / rdma_bandwidth_Bps``
  (+ the universal requester-side decompress) and the owner NOTHING on
  its serve lane (``bytes_out`` still ledgers the bytes that left its
  memory); one-sided writes mirror it. All other modeled bookkeeping
  (lanes, prefetch ledger, cache accounting) is inherited unchanged.

Unlinked outputs are evicted from every table via
:meth:`invalidate_path` (wired through ``cluster.unlink``), so a freed
name can never serve stale registered bytes after a rewrite.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from repro.fanstore.accounting import WindowAccount
from repro.fanstore.backends.base import TransportBackend
from repro.fanstore.backends.shm import ShmArena
from repro.fanstore.layout import _decompress
from repro.fanstore.wire import FetchItem

__all__ = ["RdmaBackend"]


def _rkey(owner: int, path: str) -> int:
    """Deterministic rkey-style token for a registration (stable across
    the region's lifetime; NOT a secret — it models the fabric's
    protection-domain check, not authentication)."""
    h = 2166136261
    for b in f"{owner}:{path}".encode():
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


@dataclass
class _Region:
    """One registered (pinned) byte range a requester may read one-sided."""
    segment: Optional[str]      # shm segment name (None: in-process buffer)
    seg_size: int               # registered segment length
    offset: int                 # byte offset of this path inside the segment
    length: int                 # stored bytes at that offset
    token: int                  # rkey-style protection token
    compressed: bool            # requester must decompress after the read
    raw_size: int               # decompressed size (== length when raw)
    own_segment: bool           # True: this region's segment is private to
    #                             the path (outputs) and dies with it
    buffer: Optional[memoryview] = None   # the no-arena fallback mapping


class RdmaBackend(TransportBackend):
    """One-sided reads over registered ``ShmArena`` segments."""

    name = "rdma"
    measured = True

    def __init__(self, net, nodes, clocks, *, wall=None,
                 num_threads: int = 8, use_arena: Optional[bool] = None,
                 **wire_opts):
        super().__init__(net, nodes, clocks, wall=wall,
                         num_threads=num_threads, **wire_opts)
        self._use_arena = ShmArena.available if use_arena is None \
            else bool(use_arena)
        self._arena: Optional[ShmArena] = None
        # owner -> {path -> region}; partition segments are shared by every
        # region of their partition, so they are tracked separately
        self._tables: Dict[int, Dict[str, _Region]] = {}
        self._part_segs: Dict[Tuple[int, int], Tuple[str, int]] = {}
        self._reg_lock = threading.Lock()

    # ---- lifecycle ---------------------------------------------------------
    def _start_serving(self) -> None:
        if self._use_arena and self._arena is None:
            self._arena = ShmArena()

    def _stop_serving(self) -> None:
        with self._reg_lock:
            self._tables.clear()
            self._part_segs.clear()
            arena, self._arena = self._arena, None
        if arena is not None:
            arena.close()              # unlinks every registered segment

    # ---- registration (the control path) -----------------------------------
    def registration_table(self, owner: int) -> Mapping[str, _Region]:
        """The owner's published table (a snapshot copy)."""
        with self._reg_lock:
            return dict(self._tables.get(owner, {}))

    def _region(self, owner: int, path: str) -> _Region:
        tab = self._tables.get(owner)   # GIL-atomic fast path
        if tab is not None:
            hit = tab.get(path)
            if hit is not None:
                return hit
        self._lazy_start()              # raises on a closed backend
        with self._reg_lock:
            tab = self._tables.setdefault(owner, {})
            hit = tab.get(path)
            if hit is None:
                hit = self._pin(owner, path)
                tab[path] = hit
        return hit

    def _pin(self, owner: int, path: str) -> _Region:
        """Register the bytes backing ``path`` (call under _reg_lock).

        Inputs pin the WHOLE partition blob once (the region is an
        offset into the shared segment); outputs get a private segment."""
        store = self.nodes[owner]
        loc = store.locate(path)
        if loc is not None:
            pid, rec = loc
            blob = store.partition_blob(pid)
            seg = self._part_segs.get((owner, pid))
            buffer = None
            if seg is None:
                if self._arena is not None:
                    seg = self._arena.export(blob)
                    self._part_segs[(owner, pid)] = seg
                else:
                    buffer = memoryview(blob)
            return _Region(
                segment=seg[0] if seg else None,
                seg_size=seg[1] if seg else len(blob),
                offset=rec.data_offset, length=rec.stored_size,
                token=_rkey(owner, path),
                compressed=bool(rec.compressed_size),
                raw_size=rec.stat.st_size, own_segment=False,
                buffer=buffer if buffer is not None
                else (memoryview(blob) if seg is None else None))
        size = store.output_size(path)
        if size is None:
            raise FileNotFoundError(path)
        data = bytes(store.serve_remote_view(path))
        if self._arena is not None:
            name, seg_size = self._arena.export(data)
            return _Region(segment=name, seg_size=seg_size, offset=0,
                           length=len(data), token=_rkey(owner, path),
                           compressed=False, raw_size=len(data),
                           own_segment=True)
        return _Region(segment=None, seg_size=len(data), offset=0,
                       length=len(data), token=_rkey(owner, path),
                       compressed=False, raw_size=len(data),
                       own_segment=True, buffer=memoryview(data))

    def invalidate_path(self, path: str) -> None:
        """Unlink notification: evict every registration of ``path`` and
        release output-private segments (a rewrite of the freed name must
        re-register, never serve the dead bytes)."""
        with self._reg_lock:
            for tab in self._tables.values():
                region = tab.pop(path, None)
                if (region is not None and region.own_segment
                        and region.segment is not None
                        and self._arena is not None):
                    self._arena.drop(region.segment)

    def drop_node(self, node_id: int) -> None:
        """Membership: tear down a dead owner's registration table and
        release its pinned partition segments. Requesters that still hold
        a pre-drop region keep a valid mapping until the arena closes —
        exactly the fabric's behaviour, where deregistration invalidates
        NEW lookups, not in-flight reads."""
        with self._reg_lock:
            tab = self._tables.pop(node_id, None)
            segs = [name for (own, _pid), (name, _sz)
                    in list(self._part_segs.items()) if own == node_id]
            for key in [k for k in self._part_segs if k[0] == node_id]:
                del self._part_segs[key]
            if tab is not None and self._arena is not None:
                segs.extend(r.segment for r in tab.values()
                            if r.own_segment and r.segment is not None)
            if self._arena is not None:
                for name in segs:
                    self._arena.drop(name)

    # ---- the one-sided verbs -----------------------------------------------
    def read_region(self, region: _Region, token: int) -> bytes:
        """One-sided read: copy the registered bytes out of the segment.
        The owner's CPU is not involved; a wrong rkey is the fabric's
        protection fault."""
        if token != region.token:
            raise PermissionError(
                f"rdma: rkey {token:#x} does not match registration")
        if region.buffer is not None:
            view = region.buffer
        else:
            assert self._arena is not None
            view = self._arena.view(region.segment, region.seg_size)
        return bytes(view[region.offset:region.offset + region.length])

    def _move_fetch(self, requester: int, owner: int,
                    items: Sequence[FetchItem], materialize: bool,
                    verb: str, views: bool = False
                    ) -> Tuple[List[bytes], int]:
        if not materialize:
            return [b"" for _ in items], 0
        store = self.nodes[owner]
        out: List[bytes] = []
        for it in items:
            region = self._region(owner, it.path)
            raw = self.read_region(region, region.token)
            if region.compressed:      # requester-side decode: one-sided
                raw = _decompress(store.codec, raw, region.raw_size)
            out.append(raw)
        return out, 0   # the no-serve-lane contract: owner CPU never ran

    def _move_put(self, writer: int, owner: int,
                  pairs: Sequence[Tuple[FetchItem, bytes]]) -> int:
        # one-sided write into the owner's pre-negotiated staging region;
        # commit (joining the chunks) remains the cluster's publish step
        store = self.nodes[owner]
        for item, data in pairs:
            store.stage_output(writer, item.path, data)
        return 0

    # ---- the one-sided cost model (the accounting seams) -------------------
    def _account_remote(self, requester: int, owner: int,
                        items: Sequence[FetchItem], *,
                        round_trips: Optional[int] = None,
                        lane: str = "consume",
                        tenant: Optional[str] = None) -> None:
        """One-sided modeled cost: the requester pays a registration-table
        lookup per trip plus line-rate bytes (plus the universal
        requester-side decompress); the owner's serve lane accrues ZERO —
        only its ``bytes_out`` ledgers the bytes that left its memory.
        Lane bookkeeping mirrors the base exactly (including the
        serve-app lane's per-tenant attribution)."""
        trips = len(items) if round_trips is None else round_trips
        stored = sum(it.stored for it in items)
        clock = self.clocks[requester]
        cost = (trips * self.net.rdma_lookup_s
                + stored / self.net.rdma_bandwidth_Bps)
        for it in items:
            if it.compressed:
                cost += it.size / self.net.decompress_Bps
        if lane == "prefetch":
            clock.prefetch_s += cost
            clock.prefetch_bytes += stored
            clock.prefetch_windows += trips
            clock.prefetch_log.append(WindowAccount(
                owner=owner, files=len(items), bytes=stored, cost_s=cost))
        elif lane == "serve_app":
            clock.attribute_tenant(tenant or "anon", nbytes=stored,
                                   cost_s=cost, requests=trips)
        else:
            clock.consume_s += cost
            clock.bytes_in += stored
        self.clocks[owner].bytes_out += stored

    def _account_put(self, writer: int, owner: int, stored: int,
                     trips: int, lane: str) -> None:
        """One-sided write: writer pays lookup + line-rate bytes on its
        lane; the owner's serve lane accrues ZERO (the bytes land in its
        registered staging without its CPU)."""
        cost = (trips * self.net.rdma_lookup_s
                + stored / self.net.rdma_bandwidth_Bps)
        self._accrue_write(writer, cost, stored, trips, lane)
