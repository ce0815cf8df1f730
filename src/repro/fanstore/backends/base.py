"""The transport backend seam: one verb surface, interchangeable wires.

Every byte in the cluster crosses a :class:`TransportBackend`. The base
class owns everything the backends must agree on — the verb surface the
engine calls (``fetch_local`` / ``fetch_local_many`` / ``fetch_remote`` /
``fetch_remote_batch`` / ``fetch_window`` / ``prefetch_local`` /
``put_local`` / ``put_remote_batch``), the *modeled* cost accounting
those verbs accrue onto the per-node ``NodeClock`` timelines (identical
for every backend, so modeled quantities never depend on which wire
moved the bytes), the shared thread pool behind the async ``submit``
API, and the lifecycle (``start``/``close``, context manager).

Subclasses override only the two payload-movement primitives:

* :meth:`_move_fetch` — how bytes travel from an owner's ``NodeStore`` to
  the requester;
* :meth:`_move_put` — how output chunks travel to the placement owner's
  staging area.

Two-sided wires (modeled / socket / shm) share the base cost model
verbatim, so their modeled quantities never depend on which wire moved
the bytes. A backend whose FABRIC genuinely differs (the RDMA backend's
one-sided reads involve no owner CPU) additionally overrides the two
accounting seams — :meth:`_account_remote` / :meth:`_account_put` — and
documents the deviation; the lane bookkeeping (prefetch ledger, write
lane split) stays the base's job either way.

A backend that sets ``measured = True`` additionally gets wall-clock
accounting for free: the base times every movement with
``time.perf_counter_ns`` and accrues the duration onto the requester's
measured :class:`~repro.fanstore.accounting.WallClock` lane, plus the
server-side handling time (returned by ``_move_fetch``/``_move_put``)
onto the owner's measured serve lane. The modeled backend leaves the
wall clocks untouched — ``ClusterAccounting`` then reports whichever
view exists.

Callers hand the verbs resolved :class:`~repro.fanstore.wire.FetchItem`
tuples (path + sizes); the backend knows nothing about placement or
metadata.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.fanstore.accounting import NodeClock, WallClock, WindowAccount
from repro.fanstore.metrics import SPANS
from repro.fanstore.store import NodeStore
from repro.fanstore.wire import FetchItem, WireCodecPolicy

__all__ = ["TransportBackend"]


class TransportBackend:
    """Moves payloads between node stores; accounts modeled (and, for real
    wires, measured) cost. Abstract over the movement mechanism only."""

    #: registry name ("modeled" / "socket" / "shm" / "rdma")
    name = "base"
    #: True when the backend performs real transfers worth wall-clock timing
    measured = False
    #: True when :meth:`_move_fetch` serves a request with one
    #: ``NodeStore.serve_many`` pass, which copies raw input records
    #: straight from the owner's partition blobs, or hands them out as
    #: views where the caller takes them (``read_many`` counts such files
    #: in its ``files_gathered``)
    gathers = False

    def __init__(self, net, nodes: Dict[int, NodeStore],
                 clocks: Dict[int, NodeClock], *,
                 wall: Optional[Dict[int, WallClock]] = None,
                 num_threads: int = 8, stripes: int = 1,
                 pipeline_depth: int = 4, wire_codec: str = "none",
                 wire_policy: Optional[Dict[str, float]] = None,
                 lock: Optional[threading.RLock] = None):
        self.net = net
        self.nodes = nodes
        self.clocks = clocks
        self.wall = wall if wall is not None else {
            i: WallClock() for i in nodes}
        # wire tuning lives on the base so ClusterSpec can plumb it to ANY
        # backend uniformly; wires without connections (modeled/shm/rdma)
        # simply never consult stripes/pipeline, and the codec policy is
        # validated here either way (a bad wire_codec fails at build time)
        self.stripes = max(1, int(stripes))
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.wire_policy = WireCodecPolicy(codec=wire_codec,
                                           **dict(wire_policy or {}))
        # clock accrual from pool threads. When the cluster wires in
        # ClusterAccounting.lock here, accrual and snapshot/reset/flush
        # serialize on ONE lock — the consistency contract accounting.py
        # documents. Standalone construction keeps a private lock.
        self._lock = lock if lock is not None else threading.Lock()
        self._lifecycle = threading.Lock()  # start/close state transitions
        self._pool: Optional[ThreadPoolExecutor] = None
        self._num_threads = num_threads
        self._started = False
        self._closed = False
        # fault-injection seam: a FaultInjector installed by the cluster;
        # every movement consults it BEFORE bytes move, so an injected
        # fault is indistinguishable from a real dead peer downstream
        self._faults = None

    def set_faults(self, injector) -> None:
        """Install a :class:`repro.fanstore.faults.FaultInjector` (or None
        to disable). All verbs consult it before moving bytes."""
        self._faults = injector

    def _maybe_inject(self, requester: int, owner: int, verb: str) -> None:
        """Ask the injector about one operation; raises the injected
        exception, and books any injected straggler delay as retry-free
        latency on the requester's modeled consume lane."""
        if self._faults is None:
            return
        delay = self._faults.check(requester, owner, verb)
        if delay > 0.0:
            if self.measured:
                time.sleep(delay)
            with self._lock:
                self.clocks[requester].consume_s += delay

    # ---- lifecycle ---------------------------------------------------------
    def start(self) -> "TransportBackend":
        """Bring the wire up (idempotent). The modeled backend has nothing
        to start; the socket backend spawns its per-node serving loops.
        Explicit ``start()`` also REOPENS a closed backend; the lazy path
        remote verbs use (:meth:`_lazy_start`) refuses to, so an
        undrained pool task racing ``close()`` errors instead of silently
        respawning serving loops the teardown will never see."""
        with self._lifecycle:
            if not self._started:
                self._started = True
                self._closed = False
                self._start_serving()
        return self

    def _lazy_start(self) -> None:
        """Bring the wire up from a verb (exactly once, locked). Unlike
        :meth:`start` this raises on a closed backend: the only way to get
        here after ``close()`` is an in-flight task the caller failed to
        drain, and respawning serving loops for it would leak them."""
        with self._lifecycle:
            if self._closed:
                raise RuntimeError(
                    "transport backend is closed (drain futures before "
                    "close(), or call start() to reopen)")
            if not self._started:
                self._started = True
                self._start_serving()

    def close(self) -> None:
        """Deterministic teardown: stop serving loops, drop connections,
        and join the shared I/O pool. Idempotent; the backend may be
        restarted with :meth:`start` afterwards. The state flip is locked
        against :meth:`start`; the joins run outside the lock so an
        in-flight pool task that lazily calls ``start()`` cannot deadlock
        the shutdown (callers drain their futures before closing)."""
        with self._lifecycle:
            if self._closed:
                return
            self._closed = True
            self._started = False
            pool, self._pool = self._pool, None
        self._stop_serving()
        if pool is not None:
            pool.shutdown(wait=True)

    # legacy name from the PR-1 Transport; same full teardown
    shutdown = close

    def __enter__(self) -> "TransportBackend":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def _start_serving(self) -> None:
        """Subclass hook: spawn serving loops / map segments."""

    def _stop_serving(self) -> None:
        """Subclass hook: join serving loops, close connections."""

    def invalidate_path(self, path: str) -> None:
        """A committed output was unlinked: drop any transport-held state
        for the name (the RDMA backend's registration table caches
        path -> segment mappings that must never serve a deleted payload).
        No-op for wires that hold no per-path state."""

    def drop_node(self, node_id: int) -> None:
        """Membership hook: ``node_id`` is dead — tear down any per-peer
        transport state (the socket backend closes the dead peer's serving
        loop and every stripe dialed to/from it; rdma drops its registered
        segments). No-op for wires that hold no per-peer state. Must be
        safe to call for a node that was never started, and must make
        future fetches against the node fail fast with a ConnectionError
        rather than hang."""

    def ensure_node(self, node_id: int) -> None:
        """Membership hook: ``node_id`` (re)joined — bring up any per-peer
        transport state a started wire needs to serve it (the socket
        backend spawns its serving loop). No-op before ``start()`` and for
        wires without per-peer state."""

    def account_retry(self, requester: int, delay_s: float, *,
                      count: int = 1) -> None:
        """Book ``count`` failover retries and their backoff on the
        requester's retry ledger. Modeled wires only accrue; measured
        wires really sleep the backoff first (the retried fetch is
        wall-timed like any other movement)."""
        slept_ns = 0
        if self.measured and delay_s > 0.0:
            t0 = time.perf_counter_ns()
            time.sleep(delay_s)
            slept_ns = time.perf_counter_ns() - t0
        with self._lock:
            clock = self.clocks[requester]
            clock.retries += count
            clock.retry_s += delay_s
            clock.consume_s += delay_s   # a demand retry blocks the consumer
            if self.measured:
                w = self.wall[requester]
                w.retries += count
                w.retry_ns += slept_ns

    # ---- movement primitives (the only parts a wire must provide) ----------
    def _move_fetch(self, requester: int, owner: int,
                    items: Sequence[FetchItem], materialize: bool,
                    verb: str, views: bool = False
                    ) -> Tuple[List[bytes], int]:
        """Move ``items``'s payloads from ``owner`` to ``requester``.

        ``verb`` is ``"fetch"`` / ``"fetch_batch"`` / ``"fetch_window"`` so
        a framed wire can keep the transport's intent visible. ``views``
        says the caller takes read-only views of the owner's blobs
        (``NodeStore.gather``); a wire that copies on receive ignores it.
        Returns (payloads in item order, server-side handling nanoseconds
        — 0 when the wire cannot observe it)."""
        raise NotImplementedError

    def _move_put(self, writer: int, owner: int,
                  pairs: Sequence[Tuple[FetchItem, bytes]]) -> int:
        """Ship output chunks into ``owner``'s per-(writer, path) staging.
        Returns server-side handling nanoseconds."""
        raise NotImplementedError

    # ---- measured (wall-clock) accrual -------------------------------------
    def _wall_accrue(self, node_id: int, lane: str, dt_ns: int, *,
                     bytes_in: int = 0, bytes_out: int = 0,
                     requests: int = 0, owner: Optional[int] = None,
                     serve_ns: int = 0) -> None:
        with self._lock:
            w = self.wall[node_id]
            w.accrue(lane, dt_ns)
            w.bytes_in += bytes_in
            w.requests += requests
            if owner is not None:
                ow = self.wall[owner]
                ow.accrue("serve", serve_ns)
                ow.bytes_out += bytes_out

    # ---- local tier --------------------------------------------------------
    def fetch_local(self, node_id: int, item: FetchItem, *,
                    materialize: bool = True, lane: str = "consume",
                    tenant: Optional[str] = None) -> bytes:
        """Read a file the requesting node already holds (SSD tier).

        ``lane="serve_app"`` books the cost onto the concurrent serving
        lane (attributed to ``tenant``) instead of ``consume_s`` — a
        serving tenant's local read must not serialize into the trainer's
        demand timeline."""
        return self.fetch_local_many(node_id, [item], materialize=materialize,
                                     lane=lane, tenant=tenant)[0]

    def fetch_local_many(self, node_id: int, items: Sequence[FetchItem], *,
                         materialize: bool = True, lane: str = "consume",
                         tenant: Optional[str] = None,
                         views: bool = False) -> List[bytes]:
        """Read many files the requesting node holds, in one store pass
        (``NodeStore.gather``, ``views`` as there). The ledgers come out
        as a :meth:`fetch_local` of each, in order, would leave them: each
        file's ``local_cost`` accrues on the requester's clock in item
        order (float sums depend on it), under one lock acquisition; a
        measured wire books one request and the file's bytes per file."""
        if materialize:
            t0 = time.perf_counter_ns() if self.measured else 0
            out = self.nodes[node_id].gather([it.path for it in items],
                                             views=views)
            if self.measured:
                self._wall_accrue(node_id, lane,
                                  time.perf_counter_ns() - t0,
                                  bytes_in=sum(map(len, out)),
                                  requests=len(items))
        else:
            out = [b""] * len(items)
        local_cost = self.net.local_cost
        with self._lock:
            clock = self.clocks[node_id]
            for it in items:
                cost = local_cost(it.size, compressed=it.compressed)
                if lane == "serve_app":
                    clock.attribute_tenant(tenant or "anon", nbytes=it.size,
                                           cost_s=cost, requests=1)
                else:
                    clock.consume_s += cost
                clock.local_bytes += it.size
        return out

    # ---- remote tier -------------------------------------------------------
    def fetch_remote(self, requester: int, owner: int, item: FetchItem, *,
                     materialize: bool = True, lane: str = "consume",
                     tenant: Optional[str] = None) -> bytes:
        """One synchronous round trip: one ``latency_s`` for one file."""
        data = self._timed_fetch(requester, owner, [item], materialize,
                                 "fetch", lane)[0]
        with self._lock:
            self._account_remote(requester, owner, [item], lane=lane,
                                 tenant=tenant)
        return data

    def fetch_remote_batch(self, requester: int, owner: int,
                           items: Sequence[FetchItem], *,
                           materialize: bool = True, lane: str = "consume",
                           tenant: Optional[str] = None,
                           views: bool = False) -> List[bytes]:
        """Coalesced fetch: K files from one owner, ONE round-trip latency.

        The requester pays ``latency_s`` once for the whole group and the
        owner pays one request-handling ``open_overhead_s`` (one message,
        one scatter-gather over its already-open partition blobs); per-byte
        costs are unchanged. See ``_account_remote`` for the exact model.
        ``lane="serve_app"`` routes the requester-side cost onto the
        concurrent serving lane with per-``tenant`` attribution.
        ``views``: the caller takes read-only views of the owner's blobs
        where the wire serves by ``NodeStore.serve_many`` (see
        :meth:`_move_fetch`).
        """
        if not items:
            return []
        with SPANS.span("fanstore.fetch.remote") as span:
            out = self._timed_fetch(requester, owner, items, materialize,
                                    "fetch_batch", lane, views)
            if span:
                t0 = time.perf_counter_ns()
            with self._lock:
                self._account_remote(requester, owner, items, round_trips=1,
                                     lane=lane, tenant=tenant)
            if span:
                # the modeled-cost bookkeeping, waiting for the lock included
                span.counters.update(
                    files=len(items),
                    bytes=sum(it.stored for it in items),
                    account_ns=time.perf_counter_ns() - t0)
        return out

    def fetch_window(self, requester: int, owner: int,
                     items: Sequence[FetchItem], *,
                     materialize: bool = True) -> List[bytes]:
        """Scheduled-prefetch fetch: one round trip for a whole lookahead
        WINDOW of files from one owner — the window may span many training
        batches, so the per-owner latency is amortized far beyond per-batch
        coalescing.

        Cost accrues on the requester's *prefetch lane*
        (``NodeClock.prefetch_s``), not ``consume_s``: the scheduler runs on
        the transport pool concurrently with demand reads, so makespan
        (``busy_s = max(consume, serve, prefetch)``) models the overlap
        instead of serializing prefetch behind consumption. Each call appends
        a :class:`WindowAccount` entry to the requester's per-window ledger.
        The owner's serve side is accounted identically to
        ``fetch_remote_batch`` (it answers one message either way).
        """
        if not items:
            return []
        out = self._timed_fetch(requester, owner, items, materialize,
                                "fetch_window", "prefetch")
        with self._lock:
            self._account_remote(requester, owner, items, round_trips=1,
                                 lane="prefetch")
        return out

    def _timed_fetch(self, requester: int, owner: int,
                     items: Sequence[FetchItem], materialize: bool,
                     verb: str, lane: str,
                     views: bool = False) -> List[bytes]:
        """Run the movement primitive, wall-timing it on measured wires."""
        self._maybe_inject(requester, owner, verb)
        if not self.measured:
            out, _ = self._move_fetch(requester, owner, items, materialize,
                                      verb, views)
            return out
        t0 = time.perf_counter_ns()
        out, serve_ns = self._move_fetch(requester, owner, items,
                                         materialize, verb, views)
        moved = sum(len(d) for d in out)
        self._wall_accrue(requester, lane, time.perf_counter_ns() - t0,
                          bytes_in=moved, requests=1, owner=owner,
                          bytes_out=moved, serve_ns=serve_ns)
        return out

    def prefetch_local(self, node_id: int, items: Sequence[FetchItem], *,
                       materialize: bool = True) -> List[bytes]:
        """Stage node-local files (SSD tier) into the client cache ahead of
        demand; costs accrue on the prefetch lane so the disk reads overlap
        the consume timeline."""
        t0 = time.perf_counter_ns() if self.measured else 0
        out = self.nodes[node_id].gather([it.path for it in items]) \
            if materialize else [b""] * len(items)
        total = 0
        cost = 0.0
        for it in items:
            total += it.size
            cost += self.net.local_cost(it.size, compressed=it.compressed)
        if self.measured and materialize:
            self._wall_accrue(node_id, "prefetch",
                              time.perf_counter_ns() - t0,
                              bytes_in=sum(len(d) for d in out),
                              requests=1)
        with self._lock:
            clock = self.clocks[node_id]
            clock.prefetch_s += cost
            clock.prefetch_bytes += total    # sole ledger for staged bytes
        return out

    def _account_remote(self, requester: int, owner: int,
                        items: Sequence[FetchItem], *,
                        round_trips: Optional[int] = None,
                        lane: str = "consume",
                        tenant: Optional[str] = None) -> None:
        """Accrue modeled cost; ``round_trips`` defaults to one per item.

        With ``round_trips=1`` (batched) the requester pays one ``latency_s``
        for the whole group and the owner pays one request-handling
        ``open_overhead_s``: the server answers a single message with one
        scatter-gather over its already-open partition blobs instead of K
        per-request handlings. Byte costs (NIC both sides, server storage
        read, client decompress) are per-byte and unchanged.

        ``lane="prefetch"`` books the requester side onto the concurrent
        prefetch timeline (``prefetch_s`` + per-window ledger) instead of
        ``consume_s``; ``lane="serve_app"`` books it onto the concurrent
        serving lane with per-``tenant`` attribution
        (:meth:`NodeClock.attribute_tenant`). The owner's serve side is
        lane-independent.
        """
        trips = len(items) if round_trips is None else round_trips
        stored = sum(it.stored for it in items)
        clock = self.clocks[requester]
        cost = trips * self.net.latency_s + stored / self.net.bandwidth_Bps
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
        oc = self.clocks[owner]
        oc.serve_s += trips * self.net.open_overhead_s
        oc.serve_s += stored / self.net.disk_bw_Bps
        oc.serve_s += stored / self.net.bandwidth_Bps
        oc.bytes_out += stored

    # ---- write path (output payloads ship TO the placement owner) ----------
    def put_local(self, node_id: int, pairs: Sequence[Tuple[FetchItem, bytes]],
                  *, lane: str = "write") -> None:
        """Persist output chunks on the writer's own store (writer == owner):
        per-chunk SSD-tier flush cost on the writer's chosen lane."""
        node = self.nodes[node_id]
        total = 0
        cost = 0.0
        t0 = time.perf_counter_ns() if self.measured else 0
        for item, data in pairs:
            node.stage_output(node_id, item.path, data)
            total += item.size
            cost += self.net.open_overhead_s + item.size / self.net.disk_bw_Bps
        if self.measured:
            self._wall_accrue(node_id, lane, time.perf_counter_ns() - t0,
                              requests=1)
        with self._lock:
            self._accrue_write(node_id, cost, total, len(pairs), lane)

    def put_remote_batch(self, writer: int, owner: int,
                         pairs: Sequence[Tuple[FetchItem, bytes]], *,
                         lane: str = "write",
                         round_trips: Optional[int] = None) -> None:
        """Ship output chunks to the placement owner. With ``round_trips=1``
        (the batched ``write_many`` fan-in) K chunks for one owner ride ONE
        message: the writer pays ``latency_s`` once on its lane and the
        owner handles one request (one ``open_overhead_s``) before the
        per-byte NIC + SSD-flush costs — the exact mirror of
        ``fetch_remote_batch`` on the read side. The carried metadata
        publish rides the same message (no separate forward)."""
        if not pairs:
            return
        self._maybe_inject(writer, owner, "put")
        if self.measured:
            t0 = time.perf_counter_ns()
            serve_ns = self._move_put(writer, owner, pairs)
            shipped = sum(len(d) for _, d in pairs)
            self._wall_accrue(writer, lane, time.perf_counter_ns() - t0,
                              requests=1, owner=owner, bytes_out=shipped,
                              serve_ns=serve_ns)
        else:
            self._move_put(writer, owner, pairs)
        trips = len(pairs) if round_trips is None else round_trips
        stored = sum(item.size for item, _ in pairs)
        with self._lock:
            self._account_put(writer, owner, stored, trips, lane)

    def _account_put(self, writer: int, owner: int, stored: int,
                     trips: int, lane: str) -> None:
        """Modeled cost of shipping ``stored`` output bytes in ``trips``
        messages: writer-side latency + NIC on its lane, owner-side
        request handling + NIC + SSD flush on its serve lane. The one
        overridable seam for fabrics with different write semantics
        (RDMA's one-sided writes skip the owner serve accrual entirely).
        Call under the transport lock."""
        cost = trips * self.net.latency_s + stored / self.net.bandwidth_Bps
        self._accrue_write(writer, cost, stored, trips, lane)
        oc = self.clocks[owner]
        oc.serve_s += trips * self.net.open_overhead_s
        oc.serve_s += stored / self.net.bandwidth_Bps
        oc.serve_s += stored / self.net.disk_bw_Bps

    def _accrue_write(self, node_id: int, cost: float, nbytes: int,
                      rpcs: int, lane: str) -> None:
        """Book writer-side cost: ``lane="write"`` is the concurrent write
        timeline (overlaps consume/prefetch in ``busy_s``); ``"consume"``
        is the legacy serialized path ``write_file``/``commit_write`` keeps."""
        clock = self.clocks[node_id]
        if lane == "write":
            clock.write_s += cost
            clock.write_bytes += nbytes
            clock.write_rpcs += rpcs
        else:
            clock.consume_s += cost

    # ---- cache tier (accounting only; payload comes from the cache) --------
    def account_cache_hit(self, node_id: int, item: FetchItem, *,
                          worker_id: int = 0, lane: str = "consume",
                          tenant: Optional[str] = None,
                          job: Optional[str] = None) -> None:
        """A client-cache hit: RAM-speed consume cost on the node, plus
        per-worker (and per-job) attribution (co-located workers share
        the node tier, so the breakdown is the only record of WHOSE read
        hit). On the serve-app lane the RAM cost lands on the concurrent
        serving timeline and the bytes are attributed to ``tenant`` as
        well."""
        with self._lock:
            clock = self.clocks[node_id]
            cost = self.net.cache_cost(item.size)
            if lane == "serve_app":
                clock.attribute_tenant(tenant or "anon", nbytes=item.size,
                                       cost_s=cost)
            else:
                clock.consume_s += cost
            clock.attribute_cache(worker_id, hit=True, nbytes=item.size,
                                  job=job)

    def account_cache_miss(self, node_id: int, *, worker_id: int = 0,
                           job: Optional[str] = None) -> None:
        with self._lock:
            self.clocks[node_id].attribute_cache(worker_id, hit=False,
                                                 job=job)

    def account_cache_eviction(self, node_id: int, count: int = 1) -> None:
        with self._lock:
            self.clocks[node_id].cache_evictions += count

    # ---- async future API --------------------------------------------------
    @property
    def pool(self) -> ThreadPoolExecutor:
        with self._lifecycle:
            if self._closed:
                # same contract as _lazy_start: submitting after close()
                # must error, not silently respawn workers that no further
                # close() would ever join
                raise RuntimeError(
                    "transport backend is closed (drain futures before "
                    "close(), or call start() to reopen)")
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self._num_threads,
                    thread_name_prefix="fanstore-io")
            return self._pool

    def submit(self, fn: Callable, *args, **kwargs) -> Future:
        """Run any fetch callable on the shared I/O pool; returns a Future."""
        return self.pool.submit(fn, *args, **kwargs)

    def fetch_remote_batch_async(self, requester: int, owner: int,
                                 items: Sequence[FetchItem], *,
                                 materialize: bool = True,
                                 lane: str = "consume",
                                 tenant: Optional[str] = None) -> Future:
        return self.submit(self.fetch_remote_batch, requester, owner, items,
                           materialize=materialize, lane=lane, tenant=tenant)

    def fetch_window_async(self, requester: int, owner: int,
                           items: Sequence[FetchItem], *,
                           materialize: bool = True) -> Future:
        return self.submit(self.fetch_window, requester, owner, items,
                           materialize=materialize)
