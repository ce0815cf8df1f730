"""Multi-node FanStore deployment composed from the layered I/O engine.

The container has one host, so multi-node behaviour is *simulated*: N
``NodeStore`` instances wired together by four layers, each independently
pluggable (paper §5.1/§6 plus the beyond-paper scaling seams):

  placement   which node owns a path (ModuloPlacement = paper-faithful
              ``hash % N``; RingPlacement = consistent hashing for
              elasticity) and which replica serves a read
              (least-loaded / power-of-two-choices)
  transport   a pluggable backend behind one verb seam
              (``backend="modeled"|"socket"|"shm"``): the modeled
              in-process wire (InterconnectModel cost accounting), a real
              framed-TCP wire with one serving loop per node, or the
              zero-copy shared-memory fast path for co-located workers —
              all with the batched ``fetch_remote_batch`` that coalesces
              requests per (requester, owner) pair into one round trip
              and a thread-pool future API for async fetch
  cache       optional per-node byte-budget LRU read cache in front of
              both tiers (off by default; Hoard-style client caching)
  accounting  per-node NodeClock (modeled) + WallClock (measured)
              timelines and the cluster aggregates the scaling
              benchmarks plot

The real-wire backends spawn serving loops and keep connections, so a
cluster is a resource: use it as a context manager (or call ``close()``)
to tear the transport down deterministically.

``FanStoreCluster`` composes them behind the same public surface the seed
monolith had (``read``/``stat``/``write_file``/...), plus the batched
``read_many``/``write_many`` APIs the data pipeline, checkpoint writer,
and benchmarks use. Most callers should sit one level higher, on the
descriptor-based :class:`repro.fanstore.api.FanStoreSession`.

Output files are first-class citizens of the namespace: committed payloads
live on the placement owner (``RingPlacement``-routable), reads of them ride
the same local/remote/batched read machinery as inputs, and ``readdir``
merges both namespaces.

Also implemented here, beyond the paper's §5.6 (which punts resilience to
checkpoints): replica failover, straggler mitigation via replica selection,
and elastic membership hooks (see repro.train.elastic for the planner).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

from repro.fanstore.accounting import ClusterAccounting, NodeClock
from repro.fanstore.cache import ByteCache, NodeCacheTier
from repro.fanstore.layout import iter_partition, pack_partition
from repro.fanstore.metrics import SPANS
from repro.fanstore.metadata import (FileLocation, MetadataTable, StatRecord,
                                     modulo_placement, path_hash)
from repro.fanstore.placement import Placement, ReplicaSelector
from repro.fanstore.backends import make_backend
from repro.fanstore.backends.modeled import InterconnectModel
from repro.fanstore.spec import ClusterSpec, WorkerContext
from repro.fanstore.store import NodeStore
from repro.fanstore.wire import FetchItem

__all__ = ["FanStoreCluster", "ClusterSpec", "InterconnectModel",
           "NodeClock", "WorkerContext"]


class FanStoreCluster:
    """N-node transient store with replicated input metadata.

    Canonical construction is topology-first::

        spec = ClusterSpec(num_nodes=8, workers_per_node=2,
                           backend="shm", cache_policy="belady",
                           cache_bytes=256 << 20)
        with FanStoreCluster.from_spec(spec) as cluster:
            session = cluster.connect(node_id=3, worker_id=1)

    The legacy ``FanStoreCluster(num_nodes, **kwargs)`` surface is a
    DEPRECATED shim: it builds the same :class:`ClusterSpec` internally
    (so every name is validated up front, with did-you-mean suggestions
    for unknown kwargs) and will be removed once no caller constructs a
    cluster without a spec.
    """

    def __init__(self, num_nodes: Optional[int] = None, *,
                 spec: Optional[ClusterSpec] = None,
                 interconnect: Optional[InterconnectModel] = None,
                 placement: Optional[Placement] = None,
                 selector: Optional[ReplicaSelector] = None,
                 **legacy_kwargs) -> None:
        if spec is not None:
            if legacy_kwargs:
                raise TypeError(
                    "pass either spec= or the legacy kwargs, not both "
                    f"(got {sorted(legacy_kwargs)})")
            if num_nodes is not None and num_nodes != spec.num_nodes:
                raise ValueError(
                    f"num_nodes={num_nodes} disagrees with "
                    f"spec.num_nodes={spec.num_nodes}")
        else:
            if num_nodes is None:
                raise TypeError("num_nodes (or spec=) is required")
            # deprecated kwargs path: capture the soup into a validated
            # spec — unknown names raise with suggestions, registry-backed
            # strings (backend/cache_policy/placement/...) fail HERE
            spec = ClusterSpec.from_kwargs(
                num_nodes, interconnect=interconnect, placement=placement,
                selector=selector, **legacy_kwargs)
        self.spec = spec
        self.codec = spec.codec
        # runtime-object overrides beat the spec's serializable names so
        # custom placements/selectors/interconnects remain first-class
        self.net = interconnect if interconnect is not None \
            else spec.make_interconnect()
        self.nodes: Dict[int, NodeStore] = {
            i: NodeStore(i, codec=spec.codec)
            for i in range(spec.num_nodes)}
        self.metadata = MetadataTable()        # replicated input metadata
        self.output_meta: Dict[int, Dict[str, StatRecord]] = {
            i: {} for i in range(spec.num_nodes)}  # per-owner output shards
        # replicated view of committed outputs (path -> stat + owning node);
        # payloads live on the placement owner's NodeStore output tier, NOT
        # on the writer — placement is routed end-to-end through the ring
        self.output_ns = MetadataTable()
        self.accounting = ClusterAccounting(range(spec.num_nodes))
        self.placement: Placement = placement or spec.make_placement()
        self.selector: ReplicaSelector = selector or spec.make_selector()
        self.backend = spec.backend
        # wire tuning declared on the spec reaches every backend; explicit
        # backend_options still win (they are the per-experiment override)
        backend_options = dict(spec.backend_options)
        backend_options.setdefault("stripes", spec.wire_stripes)
        backend_options.setdefault("wire_codec", spec.wire_codec)
        # the backend accrues clocks under the accounting lock, so
        # snapshot/reset/flush never race a half-applied accrual
        backend_options.setdefault("lock", self.accounting.lock)
        self.transport = make_backend(spec.backend, self.net, self.nodes,
                                      self.accounting.clocks,
                                      wall=self.accounting.wall,
                                      num_threads=spec.io_threads,
                                      **backend_options)
        # observability plane: one thread-safe collector per cluster. It
        # carries app-level series (record_metric) under its OWN lock and
        # bridges every accounting ledger via ClusterAccounting.snapshot()
        # at flush time — recording never contends the clock lock.
        from repro.fanstore.metrics import MetricsCollector
        self.metrics = MetricsCollector(accounting=self.accounting,
                                        cluster=self)
        self.cache_policy = spec.cache_policy
        self.workers_per_node = spec.workers_per_node
        # ONE cache tier per node, shared by its co-located workers (the
        # old per-node private ByteCache dict lives on underneath, as the
        # tier's members; see the legacy `caches` view below)
        self.cache_tiers: Dict[int, NodeCacheTier] = {
            i: NodeCacheTier(i, spec.cache_policy, spec.cache_bytes,
                             workers=spec.workers_per_node,
                             scope=spec.cache_scope,
                             policy_options=spec.cache_policy_options)
            for i in range(spec.num_nodes)}
        self.failed: set = set()
        self._lock = threading.Lock()
        self._next_partition = 0
        # fault tolerance: the injector (None unless spec.faults is set)
        # rides the transport seam; strikes count consecutive transport
        # failures per owner, and at spec.fault_threshold the owner is
        # marked failed cluster-wide (routing, prefetch, and the socket
        # backend's connections all drop it)
        self.faults = None
        policy = spec.make_fault_policy()
        if policy is not None:
            from repro.fanstore.faults import FaultInjector
            self.faults = FaultInjector(policy)
        self.transport.set_faults(self.faults)
        self.fault_threshold = spec.fault_threshold
        self._owner_strikes: Dict[int, int] = {}

    @classmethod
    def from_spec(cls, spec: ClusterSpec, *,
                  interconnect: Optional[InterconnectModel] = None,
                  placement: Optional[Placement] = None,
                  selector: Optional[ReplicaSelector] = None
                  ) -> "FanStoreCluster":
        """The canonical constructor: declared topology in, cluster out.
        The override kwargs accept runtime OBJECTS (custom placement /
        selector / interconnect) that have no serializable spec name."""
        return cls(spec=spec, interconnect=interconnect,
                   placement=placement, selector=selector)

    # ---- sessions (topology-first client surface) --------------------------
    def connect(self, node_id: int, worker_id: int = 0, **session_kwargs):
        """Open a per-worker session: the one client surface co-located
        workers share a node cache tier through. ``session_kwargs`` pass
        to :class:`repro.fanstore.api.FanStoreSession` (``mount=``,
        ``lane=``, the serving plane's ``read_lane=``/``tenant=``, and
        the multi-job seam's ``job=`` — two jobs, e.g. train + eval,
        attach to one namespace/tier with per-job cache attribution)."""
        ctx = WorkerContext(node_id, worker_id)
        if ctx.node_id not in self.nodes:
            raise ValueError(f"node_id {node_id} outside the "
                             f"{self.num_nodes}-node topology")
        if ctx.worker_id >= self.workers_per_node:
            raise ValueError(
                f"worker_id {worker_id} outside workers_per_node="
                f"{self.workers_per_node} (declare more workers in the "
                f"ClusterSpec)")
        from repro.fanstore.api import FanStoreSession
        return FanStoreSession(self, node_id, worker_id=worker_id,
                               **session_kwargs)

    # ---- composition plumbing ----------------------------------------------
    @property
    def clocks(self) -> Dict[int, NodeClock]:
        return self.accounting.clocks

    @property
    def caches(self) -> Dict[int, ByteCache]:
        """DEPRECATED single-worker view: worker 0's member cache per node
        (the shared cache itself under ``cache_scope="node"``). Kept for
        pre-topology callers; new code addresses ``cache_tiers``."""
        return {i: t.cache_for(0) for i, t in self.cache_tiers.items()}

    def clear_caches(self) -> None:
        """Drop every tier's entries (benchmark epoch resets)."""
        for tier in self.cache_tiers.values():
            tier.clear()

    @property
    def num_nodes(self) -> int:
        return len(self.nodes)

    def live_nodes(self) -> List[int]:
        return [i for i in self.nodes if i not in self.failed]

    # ---- loading -----------------------------------------------------------
    def load_partitions(self, partitions: Sequence[bytes], *,
                        replication: Optional[int] = None,
                        by_placement: bool = False) -> None:
        """Distribute partitions over nodes with replication factor R
        (default: the topology's declared ``spec.replication``).

        Default placement is round-robin: replica r of partition p goes to
        node (p + r*stride) so replicas never co-locate. With
        ``by_placement=True`` the cluster's ``Placement`` policy assigns
        owners instead (``replica_set(f"partition:{pid}", R)``): under
        ``RingPlacement`` this makes input placement elastic — adding a
        node remaps only ~1/N partitions, with no metadata reshuffle for
        the rest (the ROADMAP's elastic-membership seam).

        Either way the input metadata (path -> owner set) is replicated to
        every node (here: stored once in the shared table — all nodes see
        the identical copy by construction).
        """
        n = self.num_nodes
        if replication is None:
            replication = self.spec.replication
        if replication > n:
            raise ValueError("replication factor exceeds node count")
        stride = max(1, n // replication)
        for blob in partitions:
            pid = self._next_partition
            self._next_partition += 1
            if by_placement:
                # replica_set order matters: its head is the placement's
                # primary (under RingPlacement, the ring successor — the
                # node that keeps the partition when membership changes)
                owners = list(dict.fromkeys(self.placement.replica_set(
                    f"partition:{pid:08d}", replication)))
            else:
                owners = sorted(set(
                    (pid + r * stride) % n for r in range(replication)))
            for o in owners:
                self.nodes[o].load_partition(pid, blob)
            primary = owners[0]
            rest = tuple(o for o in owners if o != primary)
            for idx, rec in enumerate(iter_partition(blob, codec=self.codec)):
                self.metadata.insert(
                    rec.path, rec.stat,
                    FileLocation(node_id=primary, partition_id=pid,
                                 record_index=idx, replicas=rest))

    def broadcast_directory(self, prefix: str) -> int:
        """Replicate every file under ``prefix`` to all nodes (paper §5.4:
        user-specified directory, e.g. the test set). Returns files copied."""
        prefix = prefix.strip("/")
        copied = 0
        for path in list(self.metadata.paths()):
            if not path.startswith(prefix):
                continue
            st, loc = self.metadata.lookup(path)
            data = self.nodes[loc.node_id].serve_remote(path)
            blob = pack_partition([(path, data)], compress=False)
            pid = self._next_partition
            self._next_partition += 1
            new_replicas = []
            for nid, node in self.nodes.items():
                if nid not in loc.all_owners:
                    node.load_partition(pid, blob)
                    new_replicas.append(nid)
            self.metadata.insert(path, st, FileLocation(
                node_id=loc.node_id, partition_id=loc.partition_id,
                record_index=loc.record_index,
                replicas=tuple(sorted(set(loc.replicas) | set(new_replicas)))))
            copied += 1
        return copied

    # ---- failure / elasticity ----------------------------------------------
    def mark_failed(self, node_id: int) -> None:
        """Take ``node_id`` out of the membership: routing skips it
        (``_choose_owner`` / prefetch schedules), and the transport drops
        its per-peer state (the socket backend closes the dead peer's
        serving loop and every stripe dialed to or from it) so stale
        connections fail fast instead of hanging. Idempotent. Reached
        organically by the strike counter on the failover read path, or
        called directly by a membership service / test / benchmark."""
        first = node_id not in self.failed
        self.failed.add(node_id)
        if first:
            self.transport.drop_node(node_id)

    def mark_joined(self, node_id: int) -> None:
        """Admit ``node_id`` to the membership — a recovered node or a
        brand-new id (elastic scale-out). New ids get an empty
        ``NodeStore``, clocks, a cache tier, and (under ``RingPlacement``)
        a seat on the ring; recovered ids keep their stores. Either way
        the strike ledger is cleared and the transport (re)opens the
        peer. Data movement is NOT implicit — call :meth:`heal` to
        restore replication onto the new member."""
        if node_id not in self.nodes:
            self.nodes[node_id] = NodeStore(node_id, codec=self.codec)
            self.accounting.add_node(node_id)
            self.output_meta.setdefault(node_id, {})
            self.cache_tiers[node_id] = NodeCacheTier(
                node_id, self.spec.cache_policy, self.spec.cache_bytes,
                workers=self.spec.workers_per_node,
                scope=self.spec.cache_scope,
                policy_options=self.spec.cache_policy_options)
            if hasattr(self.placement, "add_node"):
                self.placement.add_node(node_id)
        self.failed.discard(node_id)
        with self._lock:
            self._owner_strikes.pop(node_id, None)
        self.transport.ensure_node(node_id)

    # legacy names (pre-churn API); same transitions
    def fail_node(self, node_id: int) -> None:
        self.mark_failed(node_id)

    def recover_node(self, node_id: int) -> None:
        self.mark_joined(node_id)

    def replicate_partition(self, pid: int, src: int, dst: int, *,
                            lane: str = "write") -> int:
        """Copy partition ``pid`` from ``src`` onto ``dst`` through the
        write path (real wire cost on the concurrent write lane), then
        extend every affected file's replica set so failover reads see
        the restored copy immediately. Returns bytes shipped."""
        blob = self.nodes[src].partition_blob(pid)
        name = f".rebalance/partition_{pid:08d}"
        item = FetchItem(path=name, size=len(blob), stored=len(blob))
        if src == dst:
            return 0
        self.transport.put_remote_batch(src, dst, [(item, blob)],
                                        lane=lane, round_trips=1)
        # the shipment paid the wire; the staged copy is install-only
        self.nodes[dst].drop_staging(src, name)
        self.nodes[dst].load_partition(pid, blob)
        with self._lock:
            for path in list(self.metadata.paths()):
                st, loc = self.metadata.lookup(path)
                if loc.partition_id != pid or dst in loc.all_owners:
                    continue
                self.metadata.insert(path, st, FileLocation(
                    node_id=loc.node_id, partition_id=pid,
                    record_index=loc.record_index,
                    replicas=tuple(loc.replicas) + (dst,)))
        return len(blob)

    def replicate_output(self, path: str, src: int, dst: int, *,
                         lane: str = "write") -> int:
        """Copy a committed output's payload from ``src`` onto ``dst``
        through the write path (real wire cost on the concurrent write
        lane), then extend its replica set so failover reads see the
        restored copy immediately — the output-tier mirror of
        :meth:`replicate_partition` (PR-7 left outputs single-owner).
        Returns bytes shipped."""
        path = path.strip("/")
        hit = self.output_ns.lookup(path)
        if hit is None:
            raise FileNotFoundError(path)
        st, loc = hit
        if src == dst or dst in loc.all_owners:
            return 0
        payload = self.nodes[src].serve_remote(path)
        item = FetchItem(path=path, size=len(payload), stored=len(payload))
        self.transport.put_remote_batch(src, dst, [(item, payload)],
                                        lane=lane, round_trips=1)
        # the shipment staged the chunk under (src, path); installing it
        # into dst's committed output tier is the local half of the copy
        self.nodes[dst].commit_output(src, path)
        with self._lock:
            cur = self.output_ns.lookup(path)
            if cur is None:          # unlinked while the copy was in flight
                self.nodes[dst].drop_output(path)
                return 0
            st, loc = cur
            if dst not in loc.all_owners:
                self.output_ns.insert(path, st, FileLocation(
                    node_id=loc.node_id, partition_id=loc.partition_id,
                    record_index=loc.record_index,
                    replicas=tuple(loc.replicas) + (dst,)))
            self.output_meta[dst][path] = st
        return len(payload)

    def heal(self, target_replication: Optional[int] = None) -> int:
        """Plan + execute one re-replication pass: restore every
        under-replicated partition AND committed output onto live nodes
        through the write path (see
        :func:`repro.train.elastic.execute_rebalance`). Returns the
        number of copies made."""
        from repro.train.elastic import execute_rebalance, plan_rebalance
        if target_replication is None:
            target_replication = self.spec.replication
        plan = plan_rebalance(self, target_replication=target_replication)
        return execute_rebalance(self, plan)

    def heal_async(self, target_replication: Optional[int] = None
                   ) -> "Future[int]":
        """Background re-replication on the transport's I/O pool — the
        churn story's 'keep serving while healing' half: demand reads
        keep failing over to surviving replicas while this future
        restores R in the background."""
        return self.transport.submit(self.heal, target_replication)

    def tick_step(self, step: int) -> None:
        """Advance the fault injector's training-step clock (drives
        ``FaultPolicy.kill_at_step``). No-op without an injector."""
        if self.faults is not None:
            self.faults.on_step(step)

    def fault_stats(self) -> Dict[str, int]:
        """The injector's counters plus the cluster retry ledger (empty
        injector counters when no FaultPolicy is configured)."""
        stats = self.faults.stats() if self.faults is not None else {
            "ops": 0, "injected": 0, "dropped": 0, "errored": 0,
            "delayed": 0, "killed": False, "step": -1}
        stats["retries"] = self.accounting.retries()
        stats["failed_nodes"] = sorted(self.failed)
        return stats

    def unreachable_paths(self) -> List[str]:
        """Input files whose every owner is failed (data loss without R>=2)."""
        lost = []
        for path in self.metadata.paths():
            _, loc = self.metadata.lookup(path)
            if all(o in self.failed for o in loc.all_owners):
                lost.append(path)
        return lost

    # ---- reads -------------------------------------------------------------
    def _fetch_item(self, path: str, st: StatRecord,
                    loc: FileLocation) -> FetchItem:
        """Resolve the sizes the transport cost model needs for one file:
        the primary owner's read record, resolved when it indexed the
        partition, or the stat's size for a file it holds no record of
        (a committed output)."""
        rec = self.nodes[loc.node_id].read_record(path)
        if rec is not None:
            return rec.item
        return FetchItem(path=path, size=st.st_size, stored=st.st_size)

    def _lookup(self, path: str) -> Tuple[StatRecord, FileLocation]:
        """Resolve a path against the replicated input metadata, falling
        back to the committed-output namespace (visible-until-finish).
        Output locations point at the placement owner holding the payload,
        so output reads ride the same local/remote/batched machinery as
        input reads."""
        hit = self.metadata.lookup(path)
        if hit is None:
            hit = self.output_ns.lookup(path)
        if hit is None:
            raise FileNotFoundError(path)
        return hit

    def _choose_owner(self, loc: FileLocation, item: FetchItem,
                      pending_serve: Dict[int, float], *,
                      avoid: Optional[int] = None) -> Optional[int]:
        """Pick the live replica that serves this fetch, propagating the
        in-batch load (``pending_serve``) so one batch spreads across
        replicas. Returns None when every owner is failed — demand paths
        raise, the prefetch path skips. Shared by ``read_many`` and
        ``prefetch_window`` so selection policy cannot drift between them.

        ``avoid`` names an owner that just failed this read: the failover
        loop prefers any OTHER live replica, falling back to the avoided
        owner itself when it is the only live one (so a transient fault
        at R=1 still gets its retries before the strike counter marks the
        node failed for good).
        """
        owners = [o for o in loc.all_owners if o not in self.failed]
        if avoid is not None and len(owners) > 1:
            owners = [o for o in owners if o != avoid]
        if not owners:
            return None
        if len(owners) == 1:
            owner = owners[0]
        else:
            load = {o: self.clocks[o].serve_s + pending_serve.get(o, 0.0)
                    for o in owners}
            owner = self.selector.choose(owners, load)
        # booked for a single owner too, so a later file of the batch with
        # several owners sees the same load
        pending_serve[owner] = pending_serve.get(owner, 0.0) + (
            self.net.local_cost(item.stored)
            + item.stored / self.net.bandwidth_Bps)
        return owner

    # ---- failover plumbing -------------------------------------------------
    def _note_owner_failure(self, owner: int, exc: BaseException) -> None:
        """One transport failure against ``owner``: bump its strike count
        and, at ``fault_threshold`` consecutive strikes, mark it failed
        cluster-wide — organic failure detection, no oracle required."""
        with self._lock:
            strikes = self._owner_strikes.get(owner, 0) + 1
            self._owner_strikes[owner] = strikes
            threshold_hit = strikes >= self.fault_threshold
        if threshold_hit and owner not in self.failed:
            self.mark_failed(owner)

    def _note_owner_ok(self, owner: int) -> None:
        """A successful fetch resets the owner's consecutive-strike count
        (only sustained failure takes a node out of rotation)."""
        if self._owner_strikes.get(owner):
            with self._lock:
                self._owner_strikes[owner] = 0

    def _retry_backoff(self, requester: int, attempt: int, *,
                       count: int = 1) -> None:
        """Capped exponential backoff for failover attempt ``attempt``
        (1-based), booked on the requester's retry ledger."""
        delay = min(self.spec.retry_backoff_cap_s,
                    self.spec.retry_backoff_s * (2 ** (attempt - 1)))
        self.transport.account_retry(requester, delay, count=count)

    def _fetch_with_failover(self, requester: int, groups: Dict[
            int, List[Tuple[int, FetchItem, FileLocation]]], *,
            materialize: bool, batched: bool, window: bool,
            on_data, lost_ok: bool, lane: str = "consume",
            tenant: Optional[str] = None,
            out: Optional[List] = None,
            views: bool = False) -> Tuple[int, int]:
        """Drain an (owner -> [(slot, item, loc)]) worklist, classifying
        owner errors and retrying on the next live replica. Returns the
        round trips that succeeded and the retries paid.

        One round fetches every group; a group whose owner raised a
        transport failure (ConnectionError / timeout / ERR frame /
        injected fault — see :func:`repro.fanstore.faults
        .is_transport_failure`) strikes that owner, pays ONE retry tick of
        capped exponential backoff, and is re-routed via
        :meth:`_choose_owner` (``avoid=`` the owner that just failed).
        Entries with no live replica left raise
        :class:`~repro.fanstore.faults.NodeLostError` naming the lost
        partitions — or are silently dropped when ``lost_ok`` (the
        best-effort prefetch path; demand reads surface the loss).
        Non-transport errors re-raise unclassified: a genuine
        ``FileNotFoundError`` must never burn replicas. Successful
        payloads are delivered through ``on_data(slot, item, data)``, or
        stored as ``out[slot]`` where ``out`` is given. ``views`` is
        passed to each batched fetch (``fetch_remote_batch``).

        Termination: every retry either removes a group (success), or
        strikes its owner — and at ``fault_threshold`` strikes the owner
        is marked failed and drops out of ``_choose_owner`` for good, so
        the live-owner set is strictly shrinking along any failure path.
        ``max_attempts`` is a belt-and-suspenders valve on top.
        """
        from repro.fanstore.faults import NodeLostError, is_transport_failure
        attempt = 0
        max_attempts = (self.fault_threshold + 1) * max(2, len(self.nodes))
        trips = retries = 0
        while groups:
            attempt += 1
            failed: List[Tuple[
                int, List[Tuple[int, FetchItem, FileLocation]],
                BaseException]] = []
            for owner, entries in list(groups.items()):
                items = [it for _, it, _ in entries]
                try:
                    if window:
                        datas = self.transport.fetch_window(
                            requester, owner, items, materialize=materialize)
                    elif batched:
                        datas = self.transport.fetch_remote_batch(
                            requester, owner, items, materialize=materialize,
                            lane=lane, tenant=tenant, views=views)
                    else:
                        datas = [self.transport.fetch_remote(
                            requester, owner, it, materialize=materialize,
                            lane=lane, tenant=tenant)
                            for it in items]
                except Exception as exc:
                    if not is_transport_failure(exc):
                        raise
                    self._note_owner_failure(owner, exc)
                    failed.append((owner, entries, exc))
                    continue
                self._note_owner_ok(owner)
                trips += 1 if window or batched else len(items)
                del groups[owner]
                if out is not None:
                    for (slot, _, _), data in zip(entries, datas):
                        out[slot] = data
                    continue
                for (slot, item, _), data in zip(entries, datas):
                    on_data(slot, item, data)
            if not failed:
                continue
            # one retry tick per failed group, one shared backoff level
            self._retry_backoff(requester, min(attempt, 16),
                                count=len(failed))
            retries += len(failed)
            last_exc = failed[-1][2]
            regroup: Dict[int, List[
                Tuple[int, FetchItem, FileLocation]]] = {}
            pending_serve: Dict[int, float] = {}
            lost: List[Tuple[str, int]] = []
            exhausted = attempt >= max_attempts
            for owner, entries, _ in failed:
                for slot, item, loc in entries:
                    new_owner = None if exhausted else self._choose_owner(
                        loc, item, pending_serve, avoid=owner)
                    if new_owner is None:
                        lost.append((item.path, loc.partition_id))
                    else:
                        regroup.setdefault(new_owner, []).append(
                            (slot, item, loc))
            if lost and not lost_ok:
                raise NodeLostError.for_items(lost) from last_exc
            groups = regroup
        return trips, retries

    def read(self, requester: int, path: str, *, worker_id: int = 0,
             materialize: bool = True, lane: str = "consume",
             tenant: Optional[str] = None,
             job: Optional[str] = None) -> bytes:
        """Whole-file read as the training process sees it (paper §3.4).

        ``materialize=False`` runs the identical placement + timeline
        accounting but skips the payload copies — used by the scaling
        benchmarks, where 512 nodes x thousands of multi-MB reads would
        spend their wall time in host memcpy instead of the modeled fabric.
        Always owning ``bytes`` (``b""`` unmaterialized), never a view.
        """
        return self.read_many(requester, [path], worker_id=worker_id,
                              materialize=materialize, batched=False,
                              lane=lane, tenant=tenant, job=job)[0]

    def read_many(self, requester: int, paths: Sequence[str], *,
                  worker_id: int = 0, materialize: bool = True,
                  batched: bool = True, lane: str = "consume",
                  tenant: Optional[str] = None,
                  job: Optional[str] = None) -> List[bytes]:
        """Batched read: all remote requests for one owner ride ONE round trip.

        Returns read-only bytes-like objects, in input order: ``bytes``, or
        a ``memoryview`` over storage that FanStore never mutates, valid
        for as long as the caller holds it (the view keeps its partition
        blob alive, past a ``drop_partition`` or a node's death). Views
        come from the one-pass gather (below): an input record stored
        raw, read from the requester's own partitions or, on a wire that
        serves by ``NodeStore.serve_many`` (the in-process modeled wire),
        from an owner's. Everything else is owning ``bytes``: LZSS
        records, committed outputs, files a descriptor holds open, the
        remote legs of wires that copy on receive, every read with the
        cache tier on (no view is ever stored in a tier), and every read
        with ``batched=False`` or ``materialize=False``. A caller that
        needs ``bytes`` methods takes ``bytes(d)``.

        ``batched=False`` degrades to per-file round trips (the paper's
        synchronous client), byte-for-byte identical to the seed ``read``
        accounting — benchmarks compare the two to show the coalescing win.
        Results are returned in input order. ``worker_id`` names which of
        the requester node's co-located workers is reading: the node's
        shared cache tier serves them all, with per-worker hit/miss
        attribution (modeled costs are worker-independent by contract).

        ``lane="serve_app"`` is the tenant-aware read verb the serving
        plane (:mod:`repro.fanstore.serving`) drives: every cost lands on
        the concurrent ``NodeClock.serve_app_s`` timeline attributed to
        ``tenant``, so hundreds of read-mostly serving tenants overlap —
        rather than serialize into — the trainer's demand lane.

        ``job`` names which attached job (e.g. ``"train"`` vs ``"eval"``)
        issued the read: every cache hit/miss is additionally booked onto
        that job's attribution row on BOTH the tier ledger and the
        ``NodeClock``, so two jobs sharing one node tier tie out exactly
        against the tier totals (tenant-ledger discipline).
        """
        if requester in self.failed:
            raise IOError(f"node {requester} is failed")
        from repro.fanstore.faults import NodeLostError
        out: List[Optional[bytes]] = [None] * len(paths)
        tier = self.cache_tiers[requester]
        cached = tier.enabled
        node = self.nodes[requester]
        # the one-pass gather: with no cache tier to consult or fill, a
        # batched, materialized read takes its local files in one store
        # pass after the plan (their costs accrue in path order, as the
        # per-file reads would) and stores remote payloads straight into
        # ``out``; raw records come back as views of their blobs, since
        # nothing will store them
        gather = batched and materialize and not cached
        local: List[Tuple[int, FetchItem]] = []
        # (owner -> [(output slot, item, location)]) for the remote leg;
        # the location rides along so a failed fetch can re-route to the
        # next live replica without a second metadata pass
        groups: Dict[int, List[Tuple[int, FetchItem, FileLocation]]] = {}
        pending_serve: Dict[int, float] = {}
        # the span's self time is the plan (metadata, placement, cache
        # tier); local copies are timed apart, as local_ns, and the remote
        # leg is its child span
        span = SPANS.span("fanstore.read_many")
        timed = bool(span)
        hits = files_local = bytes_local = local_ns = 0
        with span:
            try:
                for i, raw in enumerate(paths):
                    path = raw.strip("/")
                    st, loc = self._lookup(path)
                    item = self._fetch_item(path, st, loc)
                    if cached:
                        entry = tier.get(path, worker_id=worker_id,
                                         require_data=materialize, job=job)
                        if entry is not None:
                            self.transport.account_cache_hit(
                                requester, item, worker_id=worker_id,
                                lane=lane, tenant=tenant, job=job)
                            out[i] = entry.data if materialize else b""
                            hits += 1
                            continue
                        self.transport.account_cache_miss(
                            requester, worker_id=worker_id, job=job)
                    if node.has(path) or node.has_output(path):
                        if gather:
                            local.append((i, item))
                            continue
                        if timed:
                            t0 = time.perf_counter_ns()
                        data = self.transport.fetch_local(
                            requester, item, materialize=materialize,
                            lane=lane, tenant=tenant)
                        if timed:
                            local_ns += time.perf_counter_ns() - t0
                            files_local += 1
                            bytes_local += item.size
                        out[i] = data
                        if cached:
                            ev = tier.put(path, data if materialize else None,
                                          size=item.size,
                                          worker_id=worker_id, job=job)
                            self.transport.account_cache_eviction(requester,
                                                                  ev)
                        continue
                    owner = self._choose_owner(loc, item, pending_serve)
                    if owner is None:
                        raise NodeLostError.for_items(
                            [(path, loc.partition_id)])
                    groups.setdefault(owner, []).append((i, item, loc))
            finally:
                # the gather's local leg runs even where the plan raised:
                # the per-file reads had taken every local file before the
                # one that failed
                if local:
                    if timed:
                        t0 = time.perf_counter_ns()
                    datas = self.transport.fetch_local_many(
                        requester, [it for _, it in local], lane=lane,
                        tenant=tenant, views=True)
                    if timed:
                        local_ns += time.perf_counter_ns() - t0
                        files_local += len(local)
                        bytes_local += sum(it.size for _, it in local)
                    for (slot, _), data in zip(local, datas):
                        out[slot] = data

            def deliver(slot: int, item: FetchItem, data: bytes) -> None:
                out[slot] = data
                ev = tier.put(item.path, data if materialize else None,
                              size=item.size, worker_id=worker_id, job=job)
                self.transport.account_cache_eviction(requester, ev)

            if timed:
                gathered = 0
                if gather:
                    gathered = node.count_raw(it.path for _, it in local)
                    if self.transport.gathers:
                        gathered += sum(
                            self.nodes[o].count_raw(it.path for _, it, _ in e)
                            for o, e in groups.items())
                span.counters.update(
                    files_local=files_local, bytes_local=bytes_local,
                    local_ns=local_ns, cache_hits=hits,
                    files_remote=sum(map(len, groups.values())),
                    bytes_remote=sum(it.stored for entries in groups.values()
                                     for _, it, _ in entries),
                    files_gathered=gathered)
            with SPANS.span("fanstore.read_many.remote"):
                trips, retries = self._fetch_with_failover(
                    requester, groups, materialize=materialize,
                    batched=batched, window=False, on_data=deliver,
                    lost_ok=False, lane=lane, tenant=tenant,
                    out=None if cached else out, views=gather)
            if timed:
                span.counters.update(
                    owners=trips, retries=retries,
                    files_viewed=list(map(type, out)).count(memoryview))
        return out  # type: ignore[return-value]

    def read_many_async(self, requester: int, paths: Sequence[str], *,
                        worker_id: int = 0, materialize: bool = True,
                        lane: str = "consume", tenant: Optional[str] = None,
                        job: Optional[str] = None
                        ) -> "Future[List[bytes]]":
        """Batched read on the transport's I/O pool; returns a Future."""
        return self.transport.submit(self.read_many, requester, list(paths),
                                     worker_id=worker_id,
                                     materialize=materialize,
                                     lane=lane, tenant=tenant, job=job)

    # ---- scheduled prefetch (repro.fanstore.prefetch drives this) ----------
    def prefetch_window(self, requester: int, paths: Sequence[str], *,
                        worker_id: int = 0, materialize: bool = True) -> int:
        """Stage one lookahead window into the requester's client cache.

        The window may span many training batches: every remote file is
        grouped by its serving owner and fetched with ONE
        ``Transport.fetch_window`` round trip per (requester, owner,
        window); requester-local files are staged from the SSD tier.
        All cost lands on the ``NodeClock.prefetch_s`` lane (concurrent
        with the demand timeline), payloads land in the client cache so
        the demand-path ``read_many`` hits at RAM speed, and evictions are
        mirrored onto the clock exactly like demand inserts. Files already
        cached, unknown (output files), or wholly unreachable are skipped.
        Returns the number of bytes staged.
        """
        if requester in self.failed:
            raise IOError(f"node {requester} is failed")
        tier = self.cache_tiers[requester]
        if not tier.enabled:
            raise ValueError("prefetch_window requires an enabled client "
                             "cache (cache_bytes > 0)")
        local_items: List[FetchItem] = []
        groups: Dict[int, List[Tuple[int, FetchItem, FileLocation]]] = {}
        pending_serve: Dict[int, float] = {}
        for raw in paths:
            path = raw.strip("/")
            if tier.contains(path, worker_id):
                continue
            hit = self.metadata.lookup(path)
            if hit is None:
                continue                      # output file: demand-only
            st, loc = hit
            item = self._fetch_item(path, st, loc)
            if self.nodes[requester].has(path):
                local_items.append(item)
                continue
            owner = self._choose_owner(loc, item, pending_serve)
            if owner is None:
                continue                      # unreachable: surfaces on demand
            groups.setdefault(owner, []).append((0, item, loc))
        staged = 0
        evictions = 0

        def insert(item: FetchItem, data: bytes) -> None:
            nonlocal staged, evictions
            evictions += tier.put(item.path, data if materialize else None,
                                  size=item.size, worker_id=worker_id)
            if tier.contains(item.path, worker_id):
                staged += item.size   # count only accepted entries (Belady
                                      # admission / oversize may refuse)

        if local_items:
            datas = self.transport.prefetch_local(requester, local_items,
                                                  materialize=materialize)
            for item, data in zip(local_items, datas):
                insert(item, data)
        # remote windows ride the same failover loop as demand reads —
        # but best-effort (lost_ok): an unreachable file is skipped here
        # and the demand read surfaces the NodeLostError
        self._fetch_with_failover(
            requester, groups, materialize=materialize, batched=True,
            window=True, on_data=lambda _slot, item, data:
            insert(item, data), lost_ok=True)
        if evictions:
            self.transport.account_cache_eviction(requester, evictions)
        return staged

    def prefetch_window_async(self, requester: int, paths: Sequence[str], *,
                              worker_id: int = 0, materialize: bool = True
                              ) -> "Future[int]":
        """``prefetch_window`` on the transport's I/O pool."""
        return self.transport.submit(self.prefetch_window, requester,
                                     list(paths), worker_id=worker_id,
                                     materialize=materialize)

    # ---- lifecycle ---------------------------------------------------------
    def start(self) -> "FanStoreCluster":
        """Bring the transport up (socket backend: bind + spawn the
        per-node serving loops). Idempotent; remote verbs also start the
        wire lazily, so this is only needed to pin startup cost."""
        self.transport.start()
        return self

    def close(self) -> None:
        """Deterministic teardown: stop serving loops, drop connections,
        and join the transport's I/O pool (spawned lazily by async reads).
        Safe to call twice; a closed cluster may be restarted."""
        self.transport.close()

    # legacy name (pre-lifecycle API); same full teardown
    shutdown = close

    def __enter__(self) -> "FanStoreCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def stat(self, path: str) -> StatRecord:
        st = self.metadata.stat(path)
        if st is None:
            st = self.output_ns.stat(path)     # committed outputs + their dirs
        if st is None:
            raise FileNotFoundError(path)
        return st

    def readdir(self, path: str) -> List[str]:
        """Directory listing over BOTH namespaces: immutable inputs and
        committed output files (a written file lists as soon as its close
        publishes the metadata; its parent dirs materialize with it)."""
        kids = self.metadata.readdir(path)
        okids = self.output_ns.readdir(path)
        if kids is None and okids is None:
            raise FileNotFoundError(path)
        return sorted(set(kids or []) | set(okids or []))

    def is_dir(self, path: str) -> bool:
        return self.metadata.is_dir(path) or self.output_ns.is_dir(path)

    # ---- writes ------------------------------------------------------------
    def write_file(self, writer: int, path: str, data: bytes) -> None:
        """Deprecated shim (use :class:`repro.fanstore.api.FanStoreSession`
        ``open``/``write``/``close`` or the batched :meth:`write_many`):
        open-for-write + write + close with visible-on-close semantics, one
        per-file round trip on the serialized demand lane — the seed's
        synchronous writer."""
        path = path.strip("/")
        node = self.nodes[writer]
        node.write_begin(path)
        node.write_append(path, data)
        self.commit_write(writer, path)

    def write_begin(self, writer: int, path: str) -> None:
        """Open a new output file for append on the writer node. A path
        someone already committed is only rejected at close/flush time
        (visible-until-finish: opens are local, commits are global)."""
        if writer in self.failed:
            raise IOError(f"node {writer} is failed")
        self.nodes[writer].write_begin(path.strip("/"))

    def write_append(self, writer: int, path: str, data: bytes) -> int:
        return self.nodes[writer].write_append(path.strip("/"), data)

    def abort_write(self, writer: int, path: str) -> None:
        """Discard an open write: drop the writer-side buffer AND any
        chunks already streamed to the placement owner's staging — a
        later writer of the same path must commit exactly its own bytes."""
        path = path.strip("/")
        self.nodes[writer].write_abort(path)
        self.nodes[self.placement.owner(path)].drop_staging(writer, path)

    def flush_write(self, writer: int, path: str, *,
                    lane: str = "write") -> int:
        """Stream the open write's buffered bytes to the placement owner
        (fsync semantics minus the visibility: metadata publishes on close).
        This is what lets :class:`repro.fanstore.api.CheckpointWriter`
        overlap a shard's fabric shipment with producing the next chunk —
        cost accrues on the concurrent ``write_s`` lane. Returns bytes
        shipped."""
        path = path.strip("/")
        with self._lock:
            if self.output_ns.lookup(path) is not None:
                raise PermissionError(f"{path}: single-write violated")
        chunk = self.nodes[writer].write_take(path)
        if not chunk:
            return 0
        owner = self.placement.owner(path)
        item = FetchItem(path=path, size=len(chunk), stored=len(chunk))
        if owner == writer:
            self.transport.put_local(writer, [(item, chunk)], lane=lane)
        else:
            self.transport.put_remote_batch(writer, owner, [(item, chunk)],
                                            lane=lane, round_trips=1)
        return len(chunk)

    def commit_write(self, writer: int, path: str, *,
                     lane: str = "consume") -> StatRecord:
        """Close an open write: finish the buffer, ship the remainder to
        the placement owner (payload AND metadata ride one message — the
        payload is no longer stranded on the writer), enforce single-write,
        and publish. Shared by ``write_file``, the FS layer's ``close()``
        (both on the legacy serialized ``consume`` lane), and the session
        fd path (concurrent ``write`` lane)."""
        path = path.strip("/")
        st, payload = self.nodes[writer].write_finish(path)
        owner = self.placement.owner(path)
        item = FetchItem(path=path, size=len(payload), stored=len(payload))
        if owner == writer:
            self.transport.put_local(writer, [(item, payload)], lane=lane)
        else:
            self.transport.put_remote_batch(writer, owner, [(item, payload)],
                                            lane=lane, round_trips=1)
        return self._publish(writer, owner, path, st)

    def _publish(self, writer: int, owner: int, path: str,
                 st: StatRecord) -> StatRecord:
        """Atomically commit the owner's staged chunks and publish the
        output metadata; the losing writer of a race gets PermissionError
        and its staged bytes are dropped (the committed payload survives)."""
        with self._lock:
            if self.output_ns.lookup(path) is not None:
                self.nodes[owner].drop_staging(writer, path)
                raise PermissionError(f"{path}: single-write violated")
            self.nodes[owner].commit_output(writer, path)
            self.output_ns.insert(path, st, FileLocation(
                node_id=owner, partition_id=-1, record_index=-1))
            self.output_meta[owner][path] = st
        return st

    def write_many(self, writer: int, entries: Sequence[Tuple[str, bytes]],
                   *, batched: bool = True, lane: str = "write"
                   ) -> List[StatRecord]:
        """Batched write: all payloads bound for one placement owner ride
        ONE round trip — the write-side mirror of ``read_many``. Entries
        are (path, payload) pairs; results are returned in input order.

        ``batched=False`` degrades to per-file round trips (what a loop of
        ``write_file`` calls pays) for benchmarking the fan-in win.
        ``lane`` defaults to the concurrent write timeline so bulk output
        flushes overlap demand reads and prefetch.
        """
        if writer in self.failed:
            raise IOError(f"node {writer} is failed")
        norm: List[Tuple[str, bytes]] = []
        seen = set()
        for raw, data in entries:
            path = raw.strip("/")
            if path in seen:
                raise ValueError(f"{path}: duplicated in one write_many batch")
            seen.add(path)
            norm.append((path, bytes(data)))
        with self._lock:       # fail the whole batch before shipping anything
            for path, _ in norm:
                if self.output_ns.lookup(path) is not None:
                    raise PermissionError(f"{path}: single-write violated")
        node = self.nodes[writer]
        finished: List[Tuple[str, StatRecord, bytes, int]] = []
        try:
            for path, data in norm:
                self.write_begin(writer, path)
                node.write_append(path, data)
            for path, _ in norm:
                st, payload = node.write_finish(path)
                finished.append((path, st, payload,
                                 self.placement.owner(path)))
        except BaseException:
            for path, _ in norm:
                self.abort_write(writer, path)
            raise
        groups: Dict[int, List[Tuple[FetchItem, bytes]]] = {}
        for path, st, payload, owner in finished:
            item = FetchItem(path=path, size=len(payload), stored=len(payload))
            groups.setdefault(owner, []).append((item, payload))
        for owner, pairs in groups.items():
            if owner == writer:
                self.transport.put_local(writer, pairs, lane=lane)
            elif batched:
                self.transport.put_remote_batch(writer, owner, pairs,
                                                lane=lane, round_trips=1)
            else:
                for pair in pairs:
                    self.transport.put_remote_batch(writer, owner, [pair],
                                                    lane=lane, round_trips=1)
        # publish the WHOLE batch under one lock: a concurrent conflicting
        # commit fails every entry (staging dropped), never a half-batch
        with self._lock:
            for path, st, _, owner in finished:
                if self.output_ns.lookup(path) is not None:
                    for p, _, _, o in finished:
                        self.nodes[o].drop_staging(writer, p)
                    raise PermissionError(f"{path}: single-write violated")
            out = []
            for path, st, _, owner in finished:
                self.nodes[owner].commit_output(writer, path)
                self.output_ns.insert(path, st, FileLocation(
                    node_id=owner, partition_id=-1, record_index=-1))
                self.output_meta[owner][path] = st
                out.append(st)
        return out

    def unlink(self, requester: int, path: str) -> StatRecord:
        """Delete a committed output file (output GC).

        Drops the owner-side payload AND the replicated metadata record in
        one atomic step, so the name is immediately reusable by a new
        writer (single-write applies per-lifetime of a name, not forever).
        Input files are immutable for the training lifetime — unlinking
        one raises ``PermissionError``; a missing path raises
        ``FileNotFoundError``. Returns the stat of the removed file.
        """
        if requester in self.failed:
            raise IOError(f"node {requester} is failed")
        path = path.strip("/")
        if self.metadata.lookup(path) is not None:
            raise PermissionError(
                f"{path}: input files are immutable (cannot unlink)")
        with self._lock:
            hit = self.output_ns.lookup(path)
            if hit is None:
                raise FileNotFoundError(path)
            st, loc = hit
            # replicated outputs (heal / hot promotion) hold the payload
            # on every owner — the unlink must reclaim all of them, or a
            # rewrite of the freed name could read a stale replica
            for owner in loc.all_owners:
                if owner in self.nodes:
                    self.nodes[owner].drop_output(path)
                    self.output_meta[owner].pop(path, None)
            self.output_ns.remove(path)
            # a reader may hold the dead payload in its client cache; a
            # rewrite of the freed name must never serve the old bytes
            for tier in self.cache_tiers.values():
                if tier.enabled:
                    tier.invalidate(path)
            # transports with per-path state (rdma registration tables)
            # must likewise never serve the dead payload
            self.transport.invalidate_path(path)
        return st

    def write_many_async(self, writer: int,
                         entries: Sequence[Tuple[str, bytes]], *,
                         batched: bool = True, lane: str = "write"
                         ) -> "Future[List[StatRecord]]":
        """Batched write on the transport's I/O pool; returns a Future."""
        return self.transport.submit(self.write_many, writer, list(entries),
                                     batched=batched, lane=lane)

    # ---- accounting --------------------------------------------------------
    def reset_clocks(self) -> None:
        self.accounting.reset()

    def makespan_s(self) -> float:
        return self.accounting.makespan_s()

    def measured_makespan_s(self) -> float:
        """Measured (wall-clock) counterpart of :meth:`makespan_s` — only
        nonzero after a real-wire backend (socket/shm) moved bytes."""
        return self.accounting.measured_makespan_s()

    def aggregate_bandwidth(self) -> float:
        return self.accounting.aggregate_bandwidth()

    def local_hit_rate(self) -> float:
        return self.accounting.local_hit_rate()

    def cache_hit_rate(self) -> float:
        return self.accounting.cache_hit_rate()
