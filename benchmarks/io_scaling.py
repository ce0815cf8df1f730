"""Figs 5-6: multi-node aggregated bandwidth/throughput scaling.

Simulated cluster (interconnect model accounts per-node timelines; see
repro.fanstore.cluster). GPU-cluster arm: {1,4,8,16} nodes, FDR IB 56 Gb/s.
CPU-cluster arm: {1,64,128,256,512} nodes, OPA 100 Gb/s. Each node reads
every file once (the paper's benchmark), files striped once across nodes
(R=1), so the local hit rate falls as 1/N — exactly the regime Figs 5-6
measure. Reported: aggregated bandwidth, throughput, scaling efficiency vs
the paper's chosen baselines (4 nodes GPU / 64 nodes CPU).

Beyond the paper, three engine axes::

    --batched      route reads through ``read_many`` so all requests for one
                   owner ride a single modeled round trip; reports makespan
                   for both paths and the speedup
    --prefetch     clairvoyant scheduling: the whole epoch trace is turned
                   into an EpochSchedule and driven through window-coalesced
                   async prefetch (one round trip per (requester, owner,
                   window)); demand reads hit the client cache and the
                   makespan models I/O overlapped with compute
    --cache-mb M   per-node client read cache of M MiB (2 epochs so the
                   second pass can hit), reporting cache hit rate
    --write        the write half: every node writes its outputs through
                   the batched ``write_many`` (one round trip per
                   (writer, owner) pair on the concurrent write lane) vs
                   the per-file ``write_file`` loop; reports the makespan
                   win per node count
    --workers K    K co-located workers per node reading overlapping
                   per-node sample sets: the SHARED node cache tier
                   (``cache_scope="node"``) vs private per-worker caches
                   of the same total bytes — reports hit rate and
                   makespan for both (the Hoard shared-tier claim)
    --backend B    run the SAME fixed trace over a real wire
                   (``socket``: framed TCP serving loops; ``shm``:
                   zero-copy co-located fast path) and report MEASURED
                   wall-clock makespans instead of modeled ones — the
                   repo's hardware-truth numbers. Small node counts only
                   (every node is a real serving loop on this host).

``bench_json`` packages the seed / batched / prefetched arms, the
write_many-vs-perfile arm, checkpoint-flush makespan with/without
prefetch-lane overlap, an LRU-vs-Belady hit-rate comparison, the
``workers`` block (shared tier vs private caches at K co-located
workers), and the ``measured`` block (socket vs shm on the read+write
trace PLUS measured prefetch and checkpoint-overlap arms, all
teardown-verified) as the machine-readable dict that
``benchmarks/run.py --io-json`` writes to BENCH_io.json.
"""
from __future__ import annotations

import argparse
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.data.synthetic import fixed_size_files
from repro.fanstore.api import CheckpointWriter, FanStoreSession
from repro.fanstore.cluster import FanStoreCluster, InterconnectModel
from repro.fanstore.faults import NodeLostError
from repro.fanstore.prefetch import (EpochSchedule, PrefetchScheduler,
                                     SchedulerGroup)
from repro.fanstore.prepare import prepare_dataset
from repro.fanstore.spec import ClusterSpec

FILE_SIZES = [128 * 1024, 512 * 1024, 2 * 1024 * 1024, 8 * 1024 * 1024]

GPU_NET = InterconnectModel(latency_s=1.0e-6, bandwidth_Bps=56e9 / 8,
                            disk_bw_Bps=2.0e9)
CPU_NET = InterconnectModel(latency_s=1.5e-6, bandwidth_Bps=100e9 / 8,
                            disk_bw_Bps=2.0e9)

BATCH = 32      # samples per coalesced read_many call (one training step)


def _build_cluster(nodes: int, file_size: int, count: int,
                   net: InterconnectModel, *, replication: int,
                   cache_mb: int, cache_policy: str = "lru",
                   backend: str = "modeled", workers: int = 1,
                   cache_scope: str = "node",
                   cache_bytes: Optional[int] = None,
                   backend_options: Optional[Dict] = None,
                   compressible: bool = False) -> FanStoreCluster:
    # one shared payload per size: content is timing-irrelevant here and
    # generating count x file_size of RNG bytes dominated the wall time
    # (the wire-codec arm asks for compressible text instead)
    if compressible:
        payload = (b"FanStore benchmark payload row 0123456789 "
                   * (file_size // 42 + 1))[:file_size]
    else:
        payload = bytes(np.random.default_rng(1).integers(
            0, 256, file_size, dtype=np.uint8))
    files = {f"bench/f_{i:06d}.bin": payload for i in range(count)}
    blobs, _ = prepare_dataset(files, max(nodes, 8), compress=False)
    spec = ClusterSpec(num_nodes=nodes, workers_per_node=workers,
                       replication=replication,
                       cache_bytes=cache_bytes if cache_bytes is not None
                       else cache_mb * 1024 * 1024,
                       cache_scope=cache_scope,
                       cache_policy=cache_policy,
                       backend=backend,
                       backend_options=backend_options or {})
    cluster = FanStoreCluster.from_spec(spec, interconnect=net)
    cluster.load_partitions(blobs)
    return cluster


def run_one(nodes: int, file_size: int, count: int,
            net: InterconnectModel, *, replication: int = 1,
            reads_per_node: int = 128, batched: bool = False,
            prefetch: bool = False, window: int = 4,
            cache_mb: int = 0, cache_policy: str = "lru", epochs: int = 1,
            cluster: Optional[FanStoreCluster] = None) -> Dict:
    if prefetch and cache_mb == 0:
        # the scheduler stages through the client cache; budget one epoch of
        # per-node reads (size-only placeholders under materialize=False)
        m = min(reads_per_node, count)
        cache_mb = (m * file_size) // (1024 * 1024) + 1
    if cluster is None:
        # a cluster built here is closed here: its pool threads are joined
        # before the caller goes on, not whenever the collector frees it
        with _build_cluster(nodes, file_size, count, net,
                            replication=replication, cache_mb=cache_mb,
                            cache_policy=cache_policy) as owned:
            return run_one(nodes, file_size, count, net,
                           replication=replication,
                           reads_per_node=reads_per_node, batched=batched,
                           prefetch=prefetch, window=window,
                           cache_mb=cache_mb, cache_policy=cache_policy,
                           epochs=epochs, cluster=owned)
    paths = sorted(f"bench/f_{i:06d}.bin" for i in range(count))
    cluster.reset_clocks()
    cluster.clear_caches()
    # each node reads a uniform sample of the directory: the per-node
    # timeline statistics match the paper's read-everything benchmark in
    # expectation while bounding the python-loop cost at 512 nodes
    rng = np.random.default_rng(nodes)
    m = min(reads_per_node, len(paths))
    reads = 0
    for _ in range(epochs):
        traces: Dict[int, List[List[str]]] = {}
        for nid in range(nodes):
            chosen = [paths[int(i)]
                      for i in rng.choice(len(paths), size=m, replace=False)]
            reads += len(chosen)
            traces[nid] = [chosen[s:s + BATCH]
                           for s in range(0, len(chosen), BATCH)]
        if prefetch:
            _drive_prefetched_epoch(cluster, traces, window=window)
        elif batched:
            for nid, steps in traces.items():
                for step_paths in steps:
                    cluster.read_many(nid, step_paths, materialize=False)
        else:
            for nid, steps in traces.items():
                for step_paths in steps:
                    for p in step_paths:
                        cluster.read(nid, p, materialize=False)
    bw = cluster.aggregate_bandwidth()
    t = cluster.makespan_s()
    return {"nodes": nodes, "file_size": file_size,
            "agg_MBps": bw / 1e6,
            "files_s": reads / t,
            "hit_rate": cluster.local_hit_rate(),
            "cache_hit_rate": cluster.cache_hit_rate(),
            "cache_mb": cache_mb,
            "makespan_s": t,
            "bytes_moved": sum(c.bytes_in + c.prefetch_bytes + c.local_bytes
                               for c in cluster.clocks.values()),
            "prefetch_windows": cluster.accounting.prefetch_windows(),
            "batched": batched,
            "prefetch": prefetch}


def _drive_prefetched_epoch(cluster: FanStoreCluster,
                            traces: Dict[int, List[List[str]]], *,
                            window: int) -> None:
    """One epoch with clairvoyant scheduling: windows of `window` steps ride
    ahead of the demand reads, which then hit the client cache.

    The modeled clocks are order-independent (prefetch accrues on its own
    lane), so gating each step on its own window (``wait_ready``) gives
    deterministic cache hits without changing the accounted makespan.
    """
    schedule = EpochSchedule.from_trace(traces, cluster)
    schedulers = {
        nid: PrefetchScheduler(cluster, schedule, nid, window_steps=window,
                               materialize=False)
        for nid in traces}
    num_steps = max((len(s) for s in traces.values()), default=0)
    for step in range(num_steps):
        for nid, pf in schedulers.items():
            pf.ensure(step + window)
            pf.wait_ready(step)
            steps = traces[nid]
            if step < len(steps):
                cluster.read_many(nid, steps[step], materialize=False)
    for pf in schedulers.values():
        pf.close()


def run_measured_one(backend: str, *, nodes: int = 4,
                     file_size: int = 256 * 1024, count: int = 64,
                     reads_per_node: int = 64, write_files: int = 8,
                     write_size: int = 64 * 1024,
                     repeats: int = 3) -> Dict:
    """One REAL-wire arm: drive a fixed read+write trace over ``backend``
    (``socket`` or ``shm``) and report measured wall-clock numbers.

    Unlike every other arm in this file, nothing here is modeled: bytes
    actually cross the backend (TCP frames, or zero-copy views), and the
    reported makespans come from the ``WallClock`` ledgers the backend
    accrued plus the end-to-end loop time. ``repeats`` runs the whole
    trace fresh several times and keeps the fastest (standard
    best-of-N for wall timing). Teardown is verified: a leaked
    ``fanstore-*`` thread fails the benchmark rather than hanging CI.
    """
    already = {t for t in threading.enumerate()
               if t.name.startswith("fanstore")}
    best: Optional[Dict] = None
    for _ in range(repeats):
        with _build_cluster(nodes, file_size, count, CPU_NET, replication=1,
                            cache_mb=0, backend=backend) as cluster:
            paths = sorted(f"bench/f_{i:06d}.bin" for i in range(count))
            rng = np.random.default_rng(7)
            traces = {
                nid: [paths[int(i)] for i in rng.choice(
                    len(paths), size=min(reads_per_node, count),
                    replace=False)]
                for nid in range(nodes)}
            # wire-up cost stays outside the clock: bring the serving
            # loops up AND dial every (requester, owner) connection with
            # one warm-up read per pair before timing starts — otherwise
            # the socket arm pays its TCP handshakes inside the window
            # while the shm arm pays nothing
            warm = [ns.local_paths()[0] for ns in cluster.nodes.values()
                    if ns.local_paths()]
            for nid in range(nodes):
                cluster.read_many(nid, warm)
            cluster.reset_clocks()
            t0 = time.perf_counter()
            read_bytes = 0
            for nid, chosen in traces.items():
                for s in range(0, len(chosen), BATCH):
                    for data in cluster.read_many(nid, chosen[s:s + BATCH]):
                        read_bytes += len(data)
            payload = bytes(write_size)
            for nid in range(nodes):
                cluster.write_many(nid, [
                    (f"out/n{nid:03d}/f{i:04d}.bin", payload)
                    for i in range(write_files)])
            moved = read_bytes + nodes * write_files * write_size
            elapsed = time.perf_counter() - t0
            # the measured ledgers come through the observability plane:
            # one consistent accounting snapshot via cluster.metrics
            agg = cluster.metrics.snapshot()["cluster"]
            row = {"backend": backend, "nodes": nodes,
                   "file_size": file_size, "count": count,
                   "reads_per_node": min(reads_per_node, count),
                   "elapsed_s": elapsed,
                   "measured_makespan_s": agg["measured_makespan_s"],
                   "measured_total_s": agg["measured_total_s"],
                   "measured_bytes": agg["measured_bytes"],
                   "measured_requests": agg["measured_requests"],
                   "read_bytes": read_bytes,
                   "bytes_moved": moved,
                   "throughput_MBps": moved / elapsed / 1e6
                   if elapsed else 0.0,
                   "modeled_makespan_s": agg["makespan_s"]}
        if best is None or row["elapsed_s"] < best["elapsed_s"]:
            best = row
    # only threads THIS function spawned count — a modeled arm elsewhere in
    # the process may hold a lazily-built pool whose workers die with it
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("fanstore") and t.is_alive()
              and t not in already]
    if leaked:
        raise RuntimeError(f"serving-loop teardown leaked threads: {leaked}")
    best["teardown_clean"] = True
    return best


def measured_comparison(*, smoke: bool = False) -> Dict:
    """Socket vs shared-memory on the SAME trace: the co-located zero-copy
    path must beat the framed-TCP path on real wall clocks (the Hoard
    node-local-tier claim, measured instead of modeled)."""
    kw = dict(nodes=4, count=32 if smoke else 64,
              file_size=(128 if smoke else 256) * 1024,
              reads_per_node=32 if smoke else 64,
              write_files=4 if smoke else 8)
    sock = run_measured_one("socket", **kw)
    shm = run_measured_one("shm", **kw)
    return {"config": kw, "socket": sock, "shm": shm,
            "shm_speedup_vs_socket": (
                sock["elapsed_s"] / shm["elapsed_s"]
                if shm["elapsed_s"] else 1.0),
            "teardown_clean": sock["teardown_clean"]
            and shm["teardown_clean"]}


def format_measured_rows(rows: List[Dict]) -> List[str]:
    return [(f"measured,backend={r['backend']},nodes={r['nodes']},"
             f"size={r['file_size']//1024}KB,"
             f"elapsed={r['elapsed_s']:.4f}s,"
             f"measured_makespan={r['measured_makespan_s']:.4f}s,"
             f"throughput={r['throughput_MBps']:.0f}MB/s,"
             f"requests={r['measured_requests']}") for r in rows]


# ---- the wire itself: striped/pipelined socket vs its single-conn self ------
def run_wire_arm(backend: str, *, backend_options: Optional[Dict] = None,
                 file_size: int = 1024 * 1024, count: int = 64,
                 passes: int = 3, repeats: int = 3,
                 compressible: bool = False) -> Dict:
    """Pure wire throughput: node 0 reads every REMOTE path (owned by the
    peer node) in one coalesced batch per pass — no local reads, no cache,
    so elapsed time is the transport data plane and nothing else. Reports
    MB/s plus the per-stripe and wire-codec ledgers."""
    already = {t for t in threading.enumerate()
               if t.name.startswith("fanstore")}
    best: Optional[Dict] = None
    for _ in range(repeats):
        with _build_cluster(2, file_size, count, CPU_NET, replication=1,
                            cache_mb=0, backend=backend,
                            backend_options=backend_options,
                            compressible=compressible) as cluster:
            # replication=1, 2 nodes: node 1's partition is exactly the
            # set node 0 must pull over the wire
            remote = sorted(cluster.nodes[1].local_paths())
            cluster.read_many(0, remote[:2])       # warm dials + pins
            cluster.reset_clocks()
            t0 = time.perf_counter()
            moved = 0
            for _ in range(passes):
                for data in cluster.read_many(0, remote):
                    moved += len(data)
            elapsed = time.perf_counter() - t0
            # stripe / codec / serve ledgers via the observability plane
            snap = cluster.metrics.snapshot()
            agg = snap["cluster"]
            row = {"backend": backend,
                   "options": dict(backend_options or {}),
                   "file_size": file_size, "count": count,
                   "passes": passes, "bytes_moved": moved,
                   "elapsed_s": elapsed,
                   "throughput_MBps": moved / elapsed / 1e6
                   if elapsed else 0.0,
                   "stripes_used": sorted(agg["stripe_bytes"]),
                   "wire_saved_bytes": agg["wire_saved_bytes"],
                   "serve_ns": sum(n["measured"]["serve_ns"]
                                   for n in snap["nodes"].values())}
        if best is None or row["elapsed_s"] < best["elapsed_s"]:
            best = row
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("fanstore") and t.is_alive()
              and t not in already]
    if leaked:
        raise RuntimeError(f"wire arm leaked threads: {leaked}")
    best["teardown_clean"] = True
    return best


def measured_wire_comparison(*, smoke: bool = False) -> Dict:
    """The tentpole's headline block (``measured.wire`` in BENCH_io.json):

    * ``single``  — one connection, no pipelining: the PR-4 wire.
    * ``striped`` — the full data plane (8 stripes, pipelined frames,
      vectored I/O); the guarded claim is striped >> single on the SAME
      trace and host.
    * ``rdma``    — the one-sided backend on the same trace; its serve
      ledger must be exactly zero (no owner CPU on the data path).
    * ``codec``   — LZSS-on-the-wire engages ONLY when the cost model
      predicts a win: a forced-slow modeled wire on compressible payloads
      must save bytes; the honest default policy on the same trace must
      ship everything raw.
    """
    kw = dict(file_size=(256 if smoke else 1024) * 1024,
              count=32 if smoke else 64,
              passes=2 if smoke else 3, repeats=3)
    single = run_wire_arm("socket", backend_options={
        "stripes": 1, "pipeline_depth": 1}, **kw)
    striped = run_wire_arm("socket", backend_options={
        "stripes": 8, "pipeline_depth": 4}, **kw)
    rdma = run_wire_arm("rdma", **kw)
    # codec arms ride a tiny compressible trace: the pure-Python LZSS is
    # ~40 MB/s, so the engagement proof must not dominate the bench
    ckw = dict(file_size=64 * 1024, count=16, passes=1, repeats=1,
               compressible=True)
    forced = run_wire_arm("socket", backend_options={
        "wire_codec": "lzss",
        "wire_policy": {"wire_Bps": 1e6, "compress_Bps": 1e12,
                        "decompress_Bps": 1e12, "min_bytes": 1}}, **ckw)
    honest = run_wire_arm("socket", backend_options={
        "wire_codec": "lzss"}, **ckw)
    return {"config": kw,
            # stripe legs run on threads: with one core they serialize
            # and the speedup honestly reads ~1.0 or below — run.py's
            # stripe guard is conditioned on this
            "cpu_count": os.cpu_count() or 1,
            "single": single, "striped": striped, "rdma": rdma,
            "stripe_speedup": (striped["throughput_MBps"]
                               / single["throughput_MBps"]
                               if single["throughput_MBps"] else 1.0),
            "codec": {
                "forced_saved_bytes": forced["wire_saved_bytes"],
                "honest_saved_bytes": honest["wire_saved_bytes"],
                "engages_when_predicted": forced["wire_saved_bytes"] > 0,
                "raw_when_not_predicted": honest["wire_saved_bytes"] == 0},
            "teardown_clean": single["teardown_clean"]
            and striped["teardown_clean"] and rdma["teardown_clean"]}


# ---- prefetch with room to breathe ------------------------------------------
#: a WAN-ish/parallel-FS-ish fabric: per-message latency dominates, so
#: amortizing round trips across a deep lookahead window is the whole game
#: (the regime the thin ~1-2% smoke-arm prefetch wins never showed)
SLOW_NET = InterconnectModel(latency_s=200e-6, bandwidth_Bps=10e9 / 8,
                             disk_bw_Bps=2.0e9)


def prefetch_depth_comparison(*, smoke: bool = False,
                              window: int = 16) -> Dict:
    """The config where scheduled prefetch shows its SHAPE: a slow,
    latency-bound interconnect and a deep lookahead window. Batched
    demand reads pay one round trip per (step, owner) on the consume
    timeline; the scheduler amortizes the same latency across
    ``window``-step windows AND moves the cost to the overlapped prefetch
    lane — the ratio here is the guarded prefetch win (replacing the thin
    ~1-2% wins of the fast-fabric smoke arms, which this file keeps only
    as direction checks)."""
    nodes = 8
    # small files keep the arm latency-bound (the shape under test):
    # at 64 KiB a transfer is ~50 us against a 200 us round trip, so the
    # win IS the round trips the window amortizes; big files would bury
    # it under bandwidth and serve time common to both arms
    kw = dict(file_size=64 * 1024,
              count=max(128, 2 * nodes), net=SLOW_NET,
              reads_per_node=96 if smoke else 192)
    batched = run_one(nodes, batched=True, **kw)
    prefetched = run_one(nodes, prefetch=True, window=window,
                         cache_policy="belady", **kw)
    return {"nodes": nodes, "window": window,
            "net": {"latency_s": SLOW_NET.latency_s,
                    "bandwidth_Bps": SLOW_NET.bandwidth_Bps},
            "batched_makespan_s": batched["makespan_s"],
            "prefetched_makespan_s": prefetched["makespan_s"],
            "prefetch_windows": prefetched["prefetch_windows"],
            "prefetch_speedup": (batched["makespan_s"]
                                 / prefetched["makespan_s"]
                                 if prefetched["makespan_s"] else 1.0)}


def run_workers_one(nodes: int, workers: int, file_size: int, count: int,
                    net: InterconnectModel, *, shared: bool = True,
                    reads_per_worker: int = 64, epochs: int = 2,
                    cache_policy: str = "lru") -> Dict:
    """K co-located workers per node, each demand-reading its own
    permutation of the node's sample pool through its own session —
    the multi-tenant regime the paper actually runs (§3).

    ``shared=True`` gives every node ONE cache tier its workers share
    (``cache_scope="node"``); ``shared=False`` splits the SAME total
    byte budget into private per-worker caches (``cache_scope="worker"``)
    — the like-for-like baseline. With overlapping worker traces the
    shared tier both dedupes payloads (worker A's fetch is worker B's
    RAM hit) and pools the budget, so its hit rate is strictly higher
    and the modeled makespan strictly lower (pinned in tests and by the
    io-json guards). All quantities are deterministic modeled clocks.
    """
    pool_size = min(reads_per_worker, count)
    # budget one node pool in TOTAL: the shared tier holds the whole pool,
    # each private cache holds pool/workers — same total bytes
    budget = pool_size * file_size + file_size
    cluster = _build_cluster(nodes, file_size, count, net, replication=1,
                             cache_mb=0, cache_bytes=budget,
                             cache_policy=cache_policy, workers=workers,
                             cache_scope="node" if shared else "worker")
    paths = sorted(f"bench/f_{i:06d}.bin" for i in range(count))
    cluster.reset_clocks()
    # per-node pool; each worker walks its own per-epoch permutation of it
    # (co-located data-parallel workers sampling one node-assigned shard)
    pools = {n: [paths[int(i)] for i in np.random.default_rng(n).choice(
        len(paths), size=pool_size, replace=False)] for n in range(nodes)}
    reads = 0
    for ep in range(epochs):
        traces: Dict = {}
        for n in range(nodes):
            for w in range(workers):
                rng = np.random.default_rng((n, w, ep))
                chosen = [pools[n][int(i)]
                          for i in rng.permutation(pool_size)]
                reads += len(chosen)
                traces[(n, w)] = [chosen[s:s + BATCH]
                                  for s in range(0, len(chosen), BATCH)]
        num_steps = max(len(s) for s in traces.values())
        for step in range(num_steps):     # workers interleave per step
            for (n, w), steps in traces.items():
                if step < len(steps):
                    cluster.read_many(n, steps[step], worker_id=w,
                                      materialize=False)
    # attribution must tie out three ways: per-worker sums == tier totals
    # (cache truth) == NodeClock totals (timeline mirror)
    attribution_ok = True
    per_worker_hits: Dict[str, int] = {}
    for n, tier in cluster.cache_tiers.items():
        tsum = sum(s.hits for s in tier.worker_stats.values())
        msum = sum(s.misses for s in tier.worker_stats.values())
        clock = cluster.clocks[n]
        attribution_ok &= (tsum == tier.stats.hits == clock.cache_hits)
        attribution_ok &= (msum == tier.stats.misses == clock.cache_misses)
        attribution_ok &= (
            sum(clock.worker_cache_hits.values()) == clock.cache_hits)
        for w, s in tier.worker_stats.items():
            per_worker_hits[f"n{n}w{w}"] = s.hits
    return {"nodes": nodes, "workers": workers,
            "cache_scope": "node" if shared else "worker",
            "file_size": file_size, "reads": reads,
            "budget_bytes": budget,
            "makespan_s": cluster.makespan_s(),
            "cache_hit_rate": cluster.cache_hit_rate(),
            "local_hit_rate": cluster.local_hit_rate(),
            "bytes_moved": sum(c.bytes_in + c.local_bytes
                               for c in cluster.clocks.values()),
            "attribution_ok": attribution_ok,
            "per_worker_hits": per_worker_hits}


def workers_comparison(*, nodes: int = 8, workers: int = 2,
                       smoke: bool = False) -> Dict:
    """Shared node tier vs private per-worker caches on the SAME traces
    and the SAME total byte budget — the ``workers`` block of
    BENCH_io.json (guarded: shared strictly beats private on both hit
    rate and makespan)."""
    kw = dict(file_size=(64 if smoke else 256) * 1024,
              count=max(128, 2 * nodes), net=CPU_NET,
              reads_per_worker=32 if smoke else 64, epochs=2)
    shared = run_workers_one(nodes, workers, shared=True, **kw)
    private = run_workers_one(nodes, workers, shared=False, **kw)
    return {"nodes": nodes, "workers": workers,
            "config": {k: v for k, v in kw.items() if k != "net"},
            "shared": shared, "private": private,
            "shared_speedup": (private["makespan_s"] / shared["makespan_s"]
                               if shared["makespan_s"] else 1.0),
            "hit_rate_gain": (shared["cache_hit_rate"]
                              - private["cache_hit_rate"])}


def format_workers_rows(rows: List[Dict]) -> List[str]:
    return [(f"workers,nodes={r['nodes']},workers={r['workers']},"
             f"scope={r['cache_scope']},"
             f"makespan={r['makespan_s']:.6f}s,"
             f"cache_hit={r['cache_hit_rate']:.3f},"
             f"attribution_ok={r['attribution_ok']}") for r in rows]


def run_measured_prefetch(backend: str, *, nodes: int = 4,
                          file_size: int = 128 * 1024, count: int = 64,
                          reads_per_node: int = 48, window: int = 4,
                          repeats: int = 2) -> Dict:
    """MEASURED (wall-clock) arm for the prefetch benchmark: drive a
    clairvoyant schedule over a real wire (``socket``/``shm``) with
    ``materialize=True`` so every window's bytes actually cross the
    backend, then demand-read the same trace out of the client cache.

    Mirrors :func:`run_measured_one`'s guarantees: nonzero measured time
    on the PREFETCH lane specifically, a byte ledger that ties out
    (wall-clock ``bytes_in`` == the schedulers' staged bytes — traces
    are sampled without replacement so nothing is skipped as already
    cached), and verified serving-loop teardown.
    """
    already = {t for t in threading.enumerate()
               if t.name.startswith("fanstore")}
    best: Optional[Dict] = None
    for _ in range(repeats):
        budget = min(reads_per_node, count) * file_size + file_size
        with _build_cluster(nodes, file_size, count, CPU_NET, replication=1,
                            cache_mb=0, cache_bytes=budget,
                            backend=backend) as cluster:
            paths = sorted(f"bench/f_{i:06d}.bin" for i in range(count))
            rng = np.random.default_rng(11)
            traces = {
                nid: [[paths[int(i)] for i in rng.choice(
                    len(paths), size=min(reads_per_node, count),
                    replace=False)][s:s + BATCH]
                    for s in range(0, min(reads_per_node, count), BATCH)]
                for nid in range(nodes)}
            # dial every (requester, owner) connection outside the timed
            # window, then drop the warm-up's cache/clock footprint
            warm = [ns.local_paths()[0] for ns in cluster.nodes.values()
                    if ns.local_paths()]
            for nid in range(nodes):
                cluster.read_many(nid, warm)
            cluster.clear_caches()
            cluster.reset_clocks()
            schedule = EpochSchedule.from_trace(traces, cluster)
            group = SchedulerGroup.for_schedule(cluster, schedule,
                                                window_steps=window)
            t0 = time.perf_counter()
            num_steps = max(len(s) for s in traces.values())
            for step in range(num_steps):
                group.ensure(step + window)
                group.wait_ready(step)
                for nid, steps in traces.items():
                    if step < len(steps):
                        cluster.read_many(nid, steps[step])
            group.close()
            elapsed = time.perf_counter() - t0
            # lane ledgers via the observability plane's consistent copy
            snap = cluster.metrics.snapshot()
            agg = snap["cluster"]
            per_node = snap["nodes"].values()
            row = {"backend": backend, "nodes": nodes,
                   "file_size": file_size,
                   "elapsed_s": elapsed,
                   "measured_prefetch_s": sum(
                       n["measured"]["prefetch_ns"]
                       for n in per_node) / 1e9,
                   "measured_makespan_s": agg["measured_makespan_s"],
                   "measured_bytes": agg["measured_bytes"],
                   "staged_bytes": group.bytes_scheduled,
                   "cache_hits": sum(n["modeled"]["cache_hits"]
                                     for n in per_node),
                   "cache_hit_rate": agg["cache_hit_rate"],
                   "windows": group.windows_issued}
        if best is None or row["elapsed_s"] < best["elapsed_s"]:
            best = row
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("fanstore") and t.is_alive()
              and t not in already]
    if leaked:
        raise RuntimeError(f"prefetch arm leaked threads: {leaked}")
    best["teardown_clean"] = True
    return best


def measured_prefetch_comparison(*, smoke: bool = False) -> Dict:
    """Socket vs shared-memory on the SAME scheduled trace, measured.
    The speedup compares the PREFETCH-LANE wall time (the wire leg of
    the scheduled windows, summed across nodes) — end-to-end elapsed is
    reported too, but it is diluted by identical Python drive overhead
    on both arms and would make a flaky guard."""
    kw = dict(nodes=4, count=32 if smoke else 64,
              file_size=(64 if smoke else 128) * 1024,
              reads_per_node=32 if smoke else 48)
    sock = run_measured_prefetch("socket", **kw)
    shm = run_measured_prefetch("shm", **kw)
    return {"config": kw, "socket": sock, "shm": shm,
            "shm_speedup_vs_socket": (
                sock["measured_prefetch_s"] / shm["measured_prefetch_s"]
                if shm["measured_prefetch_s"] else 1.0),
            "teardown_clean": sock["teardown_clean"]
            and shm["teardown_clean"]}


def run_measured_ckpt(backend: str, *, nodes: int = 2,
                      file_size: int = 64 * 1024, count: int = 32,
                      reads_per_node: int = 32, window: int = 4,
                      shard_bytes: int = 1 << 20,
                      chunk_bytes: int = 256 * 1024,
                      repeats: int = 2) -> Dict:
    """MEASURED arm for the checkpoint-overlap benchmark: every node's
    session streams a checkpoint shard in fsync'd chunks WHILE its
    prefetch windows are in flight, over a real wire. The wall ledgers
    must show BOTH concurrent lanes nonzero (prefetch AND write — the
    measured counterpart of the modeled overlap claim), and teardown is
    verified exactly like the other measured arms."""
    already = {t for t in threading.enumerate()
               if t.name.startswith("fanstore")}
    best: Optional[Dict] = None
    for _ in range(repeats):
        budget = min(reads_per_node, count) * file_size + file_size
        with _build_cluster(nodes, file_size, count, CPU_NET, replication=1,
                            cache_mb=0, cache_bytes=budget,
                            backend=backend) as cluster:
            paths = sorted(f"bench/f_{i:06d}.bin" for i in range(count))
            rng = np.random.default_rng(13)
            traces = {
                nid: [[paths[int(i)] for i in rng.choice(
                    len(paths), size=min(reads_per_node, count),
                    replace=False)][s:s + BATCH]
                    for s in range(0, min(reads_per_node, count), BATCH)]
                for nid in range(nodes)}
            warm = [ns.local_paths()[0] for ns in cluster.nodes.values()
                    if ns.local_paths()]
            for nid in range(nodes):
                cluster.read_many(nid, warm)
            cluster.clear_caches()
            cluster.reset_clocks()
            schedule = EpochSchedule.from_trace(traces, cluster)
            group = SchedulerGroup.for_schedule(cluster, schedule,
                                                window_steps=window)
            payload = bytes(shard_bytes)
            t0 = time.perf_counter()
            group.ensure(max(len(s) for s in traces.values()) + window)
            # shards ship while the windows above are still in flight:
            # both scheduled lanes are live in the same wall window
            for nid in range(nodes):
                writer = CheckpointWriter(cluster.connect(nid),
                                          chunk_bytes=chunk_bytes)
                writer.write_shard(f"ckpt/n{nid:03d}/shard.bin", payload)
            group.close()
            elapsed = time.perf_counter() - t0
            # both concurrent lanes read from one consistent snapshot
            snap = cluster.metrics.snapshot()
            per_node = snap["nodes"].values()
            row = {"backend": backend, "nodes": nodes,
                   "shard_bytes": shard_bytes,
                   "elapsed_s": elapsed,
                   "measured_prefetch_s": sum(
                       n["measured"]["prefetch_ns"]
                       for n in per_node) / 1e9,
                   "measured_write_s": sum(
                       n["measured"]["write_ns"]
                       for n in per_node) / 1e9,
                   "measured_makespan_s":
                       snap["cluster"]["measured_makespan_s"],
                   "measured_requests":
                       snap["cluster"]["measured_requests"]}
        if best is None or row["elapsed_s"] < best["elapsed_s"]:
            best = row
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("fanstore") and t.is_alive()
              and t not in already]
    if leaked:
        raise RuntimeError(f"checkpoint arm leaked threads: {leaked}")
    best["teardown_clean"] = True
    return best


def measured_ckpt_comparison(*, smoke: bool = False) -> Dict:
    """Socket vs shared-memory checkpoint-overlap, measured. As with the
    prefetch arm, the guard-backing speedup compares the two concurrent
    SCHEDULED lanes' wall time (prefetch + write, the actual wire legs)
    rather than elapsed."""
    kw = dict(nodes=2, count=16 if smoke else 32,
              file_size=(32 if smoke else 64) * 1024,
              reads_per_node=16 if smoke else 32,
              shard_bytes=(1 << 19) if smoke else (1 << 20))
    sock = run_measured_ckpt("socket", **kw)
    shm = run_measured_ckpt("shm", **kw)

    def lanes(r: Dict) -> float:
        return r["measured_prefetch_s"] + r["measured_write_s"]

    return {"config": kw, "socket": sock, "shm": shm,
            "shm_speedup_vs_socket": (lanes(sock) / lanes(shm)
                                      if lanes(shm) else 1.0),
            "teardown_clean": sock["teardown_clean"]
            and shm["teardown_clean"]}


def run_write_one(nodes: int, file_size: int, files_per_node: int,
                  net: InterconnectModel, *, batched: bool = True) -> Dict:
    """Every node writes its own output files. ``batched=True`` drives the
    engine's ``write_many`` (one round trip per (writer, owner) pair, the
    concurrent write lane); ``batched=False`` is the per-file
    ``write_file`` loop (one round trip per file on the serialized demand
    lane) — the seed's synchronous writer."""
    cluster = FanStoreCluster(nodes, interconnect=net)
    payload = bytes(file_size)      # shared object: single-chunk writes are
    cluster.reset_clocks()          # zero-copy, so 512 nodes stay cheap
    files = 0
    for nid in range(nodes):
        entries = [(f"out/n{nid:03d}/f{i:05d}.bin", payload)
                   for i in range(files_per_node)]
        if batched:
            cluster.write_many(nid, entries)
        else:
            for p, d in entries:
                cluster.write_file(nid, p, d)
        files += len(entries)
    return {"nodes": nodes, "file_size": file_size, "files": files,
            "makespan_s": cluster.makespan_s(),
            "write_bytes": cluster.accounting.write_bytes(),
            "write_rpcs": cluster.accounting.write_rpcs(),
            "batched": batched}


def run_checkpoint_overlap(nodes: int, file_size: int, count: int,
                           net: InterconnectModel, *,
                           reads_per_node: int = 64, window: int = 4,
                           shard_bytes: int = 4 * 1024 * 1024,
                           chunk_bytes: int = 1 * 1024 * 1024) -> Dict:
    """Checkpoint flush DURING an active prefetch window vs serialized
    write-then-prefetch.

    Overlapped: one run where every node drives a prefetched epoch while a
    ``CheckpointWriter`` streams one shard in fsync'd chunks on the
    concurrent write lane — per-node makespan is
    ``max(consume, serve, prefetch, write)``. Serialized: the same two
    workloads accrued in isolation, summed — what a writer that parks the
    data plane would pay. The modeled clocks are order-independent, so
    both are exact, deterministic quantities.
    """
    def build():
        cache_mb = (min(reads_per_node, count) * file_size) // (1 << 20) + 1
        cluster = _build_cluster(nodes, file_size, count, net, replication=1,
                                 cache_mb=cache_mb, cache_policy="belady")
        rng = np.random.default_rng(nodes)
        paths = sorted(f"bench/f_{i:06d}.bin" for i in range(count))
        m = min(reads_per_node, len(paths))
        traces = {}
        for nid in range(nodes):
            chosen = [paths[int(i)]
                      for i in rng.choice(len(paths), size=m, replace=False)]
            traces[nid] = [chosen[s:s + BATCH]
                           for s in range(0, len(chosen), BATCH)]
        return cluster, traces

    def write_shards(cluster):
        payload = bytes(shard_bytes)
        for nid in range(cluster.num_nodes):
            writer = FanStoreSession(cluster, nid).checkpoint_writer(
                chunk_bytes=chunk_bytes)
            writer.write_shard(f"ckpt/step_0/shard_{nid:03d}.npy", payload)

    # overlapped: both workloads on one set of clocks, concurrent lanes
    cluster, traces = build()
    cluster.reset_clocks()
    _drive_prefetched_epoch(cluster, traces, window=window)
    write_shards(cluster)
    overlapped = cluster.makespan_s()
    # serialized: prefetch epoch alone + write alone, summed
    cluster, traces = build()
    cluster.reset_clocks()
    _drive_prefetched_epoch(cluster, traces, window=window)
    prefetch_only = cluster.makespan_s()
    cluster2, _ = build()
    cluster2.reset_clocks()
    write_shards(cluster2)
    write_only = cluster2.makespan_s()
    serialized = prefetch_only + write_only
    return {"nodes": nodes, "shard_bytes": shard_bytes,
            "overlapped_makespan_s": overlapped,
            "serialized_makespan_s": serialized,
            "prefetch_makespan_s": prefetch_only,
            "write_makespan_s": write_only,
            "overlap_speedup": serialized / overlapped if overlapped else 1.0}


def run_write(arm: str = "cpu", *, files_per_node: int = 32,
              file_size: int = 64 * 1024) -> List[Dict]:
    scales, net = ([1, 4, 8, 16], GPU_NET) if arm == "gpu" else \
        ([1, 64, 128, 256, 512], CPU_NET)
    rows = []
    for n in scales:
        batched = run_write_one(n, file_size, files_per_node, net,
                                batched=True)
        perfile = run_write_one(n, file_size, files_per_node, net,
                                batched=False)
        batched["makespan_perfile_s"] = perfile["makespan_s"]
        batched["write_speedup"] = (
            perfile["makespan_s"] / batched["makespan_s"]
            if batched["makespan_s"] > 0 else 1.0)
        rows.append(batched)
    return rows


def format_write_rows(arm: str, rows: List[Dict]) -> List[str]:
    return [(f"write,arm={arm},nodes={r['nodes']},"
             f"size={r['file_size']//1024}KB,files={r['files']},"
             f"makespan_write_many={r['makespan_s']:.6f}s,"
             f"makespan_perfile={r['makespan_perfile_s']:.6f}s,"
             f"write_speedup={r['write_speedup']:.3f},"
             f"write_rpcs={r['write_rpcs']}") for r in rows]


def run(arm: str = "cpu", *, count: int = None, batched: bool = False,
        prefetch: bool = False, window: int = 4,
        cache_mb: int = 0, epochs: int = 1) -> List[Dict]:
    if arm == "gpu":
        scales, net = [1, 4, 8, 16], GPU_NET
        count = count or 128
    else:
        scales, net = [1, 64, 128, 256, 512], CPU_NET
        # file count must exceed the node count or the benchmark measures
        # hot-owner serialization instead of scaling (paper uses 2K-128K)
        count = count or 1024
    rows = []
    for size in FILE_SIZES:
        for n in scales:
            # F >= 2N keeps the benchmark in the scaling (not hot-owner)
            # regime while bounding the python-loop cost at large N
            c = min(count, max(256, 2 * n))
            # the prefetch arm needs its own cluster (Belady cache enabled);
            # every other arm shares one baseline build so the dataset is
            # packed once per (size, n), as before — clocks + caches are
            # reset between runs
            baseline = None
            if not prefetch:
                baseline = _build_cluster(n, size, c, net, replication=1,
                                          cache_mb=cache_mb)
            row = run_one(n, size, c, net, batched=batched,
                          prefetch=prefetch, window=window,
                          cache_mb=cache_mb,
                          cache_policy="belady" if prefetch else "lru",
                          epochs=epochs,
                          cluster=None if prefetch else baseline)
            if batched or prefetch:
                if baseline is None:
                    baseline = _build_cluster(n, size, c, net, replication=1,
                                              cache_mb=cache_mb)
                base = run_one(n, size, c, net, batched=False,
                               cache_mb=cache_mb, epochs=epochs,
                               cluster=baseline)
                row["makespan_perfile_s"] = base["makespan_s"]
                row["batched_speedup"] = (
                    base["makespan_s"] / row["makespan_s"]
                    if row["makespan_s"] > 0 else 1.0)
                if prefetch:
                    batch_arm = run_one(n, size, c, net, batched=True,
                                        cache_mb=cache_mb, epochs=epochs,
                                        cluster=baseline)
                    row["makespan_batched_s"] = batch_arm["makespan_s"]
                    row["prefetch_speedup"] = (
                        batch_arm["makespan_s"] / row["makespan_s"]
                        if row["makespan_s"] > 0 else 1.0)
            rows.append(row)
    # efficiency vs the paper's baselines
    base_n = 4 if arm == "gpu" else 64
    for size in FILE_SIZES:
        base = next(r for r in rows
                    if r["file_size"] == size and r["nodes"] == base_n)
        peak = next(r for r in rows
                    if r["file_size"] == size and r["nodes"] == scales[-1])
        peak["efficiency_vs_base"] = (
            peak["agg_MBps"] / peak["nodes"]) / (base["agg_MBps"] / base["nodes"])
    return rows


def format_rows(arm: str, fig: str, rows: List[Dict]) -> List[str]:
    out = []
    for r in rows:
        eff = r.get("efficiency_vs_base")
        line = (
            f"{fig},arm={arm},nodes={r['nodes']},"
            f"size={r['file_size']//1024}KB,agg_bw={r['agg_MBps']:.0f}MB/s,"
            f"files_s={r['files_s']:.0f},hit={r['hit_rate']:.3f}")
        if r.get("batched"):
            line += (f",makespan_batched={r['makespan_s']:.6f}s,"
                     f"makespan_perfile={r['makespan_perfile_s']:.6f}s,"
                     f"batched_speedup={r['batched_speedup']:.3f}")
        if r.get("prefetch"):
            line += (f",makespan_prefetch={r['makespan_s']:.6f}s,"
                     f"makespan_batched={r['makespan_batched_s']:.6f}s,"
                     f"prefetch_speedup={r['prefetch_speedup']:.3f},"
                     f"windows={r['prefetch_windows']}")
        if r.get("cache_mb"):       # cache enabled: report even a 0.0 rate
            line += f",cache_hit={r['cache_hit_rate']:.3f}"
        if eff:
            line += f",scale_eff={eff:.3f}"
        out.append(line)
    return out


def cache_policy_comparison(*, num_files: int = 64, file_size: int = 4096,
                            cache_files: int = 16, accesses: int = 512,
                            seed: int = 0) -> Dict:
    """LRU vs Belady vs 2Q client-cache hit rate at one byte budget on a
    uniform-random (with reuse) epoch trace — the access pattern the paper
    says defeats LRU. Belady gets the trace as its future oracle. (Legacy
    single-budget arm kept for pinning tests; ``cache_policy_sweep`` is
    the guarded BENCH block.)"""
    rng = np.random.default_rng(seed)
    paths = [f"bench/f_{i:06d}.bin" for i in range(num_files)]
    trace = [paths[int(i)]
             for i in rng.integers(0, num_files, size=accesses)]
    budget = cache_files * file_size
    out: Dict = {"budget_bytes": budget, "accesses": accesses}
    for policy in ("lru", "belady", "2q"):
        payload = bytes(file_size)
        files = {p: payload for p in paths}
        blobs, _ = prepare_dataset(files, 8, compress=False)
        cluster = FanStoreCluster(2, interconnect=CPU_NET,
                                  cache_bytes=budget, cache_policy=policy)
        cluster.load_partitions(blobs, replication=1)
        if policy == "belady":
            EpochSchedule.from_trace({1: [[p] for p in trace]}
                                     ).install_futures(cluster)
        for p in trace:
            cluster.read_many(1, [p], materialize=False)
        out[f"{policy}_hit_rate"] = cluster.caches[1].stats.hit_rate
    return out


#: the policies the sweep scores, online first, the oracle last
SWEEP_POLICIES = ("lru", "2q", "lfu", "arc", "gdsf", "predictive", "belady")


def policy_trace(kind: str, num_files: int, epochs: int,
                 seed: int = 0) -> List[str]:
    """Deterministic DL-shaped access traces (one requester):

    * ``"uniform"`` — per-epoch uniform permutation: every file exactly
      once per epoch in a fresh shuffled order. This is the paper's
      actual access pattern (global shuffle, sampling WITHOUT
      replacement), and it is adversarial for LRU: the most recently
      read file is the FARTHEST from reuse (~one full epoch away).
    * ``"zipf"`` — per-epoch zipf multiset permutation: file i appears
      ``k_i`` times per epoch with zipf-shaped ``k_i`` (the oversampled
      hot head that class-balancing / replay sampling produces),
      shuffled within the epoch. Skew + without-replacement structure:
      frequency-aware policies win, and reuse gaps are learnable.
    * ``"scan"`` — a hot working set re-read every round with one-shot
      cold scan segments interleaved: the probation-queue case 2Q
      exists for (LRU lets every scan evict the hot set).
    """
    rng = np.random.default_rng(seed)
    paths = [f"bench/f_{i:06d}.bin" for i in range(num_files)]
    trace: List[str] = []
    if kind == "uniform":
        for _ in range(epochs):
            trace.extend(paths[int(i)]
                         for i in rng.permutation(num_files))
    elif kind == "zipf":
        w = [1.0 / (i + 1) ** 1.1 for i in range(num_files)]
        reps = [max(1, round(x * 8 / w[0])) for x in w]
        epoch = [paths[i] for i in range(num_files)
                 for _ in range(reps[i])]
        for _ in range(epochs):
            order = rng.permutation(len(epoch))
            trace.extend(epoch[int(i)] for i in order)
    elif kind == "scan":
        hot = paths[:num_files // 8]
        cold = paths[num_files // 8:]
        ci = 0
        for _ in range(epochs * 4):
            hs = list(hot)
            rng.shuffle(hs)
            trace.extend(hs)
            for _ in range(max(1, len(cold) // 12)):
                trace.append(cold[ci % len(cold)])
                ci += 1
    else:
        raise ValueError(f"unknown trace kind {kind!r}")
    return trace


def cache_policy_sweep(*, num_files: int = 64, file_size: int = 4096,
                       budgets_files=(8, 16, 32), epochs: int = 6,
                       seed: int = 0, smoke: bool = False) -> Dict:
    """The guarded cache-policy BENCH block: every registered policy x
    three byte budgets x the uniform-permutation and zipf traces, driven
    through the FULL cluster read path (placement, transport accounting,
    NodeClock mirroring — not a bare ByteCache loop), plus a scan-trace
    arm pinning 2Q's probation win over LRU.

    Guarded downstream (benchmarks/run.py): ARC >= LRU and Predictive >=
    LRU on every (budget, trace) arm, Predictive closes >= 40% of the
    LRU->Belady hit-rate gap on every zipf arm, Belady stays the upper
    bound everywhere, and 2Q >= LRU on the scan arm."""
    if smoke:
        epochs = max(3, epochs // 2)
    payload = bytes(file_size)
    paths = [f"bench/f_{i:06d}.bin" for i in range(num_files)]
    files = {p: payload for p in paths}
    blobs, _ = prepare_dataset(files, 8, compress=False)

    def drive(policy: str, trace: List[str], budget_files: int) -> float:
        cluster = FanStoreCluster(2, interconnect=CPU_NET,
                                  cache_bytes=budget_files * file_size,
                                  cache_policy=policy)
        cluster.load_partitions(blobs, replication=1)
        if policy == "belady":
            EpochSchedule.from_trace({1: [[p] for p in trace]}
                                     ).install_futures(cluster)
        for p in trace:
            cluster.read_many(1, [p], materialize=False)
        hr = cluster.caches[1].stats.hit_rate
        # the NodeClock mirror must agree with the tier truth for EVERY
        # policy — the "counters mirrored identically to LRU" contract
        clock = cluster.clocks[1]
        st = cluster.cache_tiers[1].stats
        assert clock.cache_hits == st.hits, (policy, "hit mirror")
        assert clock.cache_misses == st.misses, (policy, "miss mirror")
        cluster.close()
        return hr

    out: Dict = {"num_files": num_files, "file_size": file_size,
                 "budgets_files": list(budgets_files),
                 "policies": list(SWEEP_POLICIES), "epochs": epochs}
    for kind in ("uniform", "zipf"):
        trace = policy_trace(kind, num_files, epochs, seed)
        arms: Dict = {}
        for bf in budgets_files:
            arms[str(bf)] = {pol: drive(pol, trace, bf)
                             for pol in SWEEP_POLICIES}
        out[kind] = {"accesses": len(trace), "arms": arms}
    # zipf gap closure per budget: (pred - lru) / (belady - lru)
    out["zipf_gap_closure"] = {
        bf: ((a["predictive"] - a["lru"]) / (a["belady"] - a["lru"])
             if a["belady"] > a["lru"] else 1.0)
        for bf, a in out["zipf"]["arms"].items()}
    # scan arm at a tight budget: 2Q's probation keeps the hot set
    # resident through one-shot scans that flush LRU
    scan = policy_trace("scan", num_files, epochs, seed)
    out["scan"] = {"accesses": len(scan),
                   "budget_files": num_files // 6,
                   "lru": drive("lru", scan, num_files // 6),
                   "2q": drive("2q", scan, num_files // 6)}
    return out


def cross_epoch_comparison(*, num_files: int = 24, file_size: int = 8192,
                           epochs: int = 3, steps_per_epoch: int = 6,
                           window: int = 4, cache_files: int = 16,
                           seed: int = 0, smoke: bool = False) -> Dict:
    """Cross-epoch prefetch stitching vs drain-and-refill, guarded.

    One requester reads every file once per epoch (fresh permutation) in
    ``steps_per_epoch`` batched steps, prefetched through lookahead
    windows on a latency-bound fabric, with a cache that holds 2/3 of
    the dataset (so every epoch must re-stage the evicted tail).
    ``window`` deliberately does NOT divide ``steps_per_epoch``: the
    drain-and-refill baseline (one schedule per epoch, fully drained at
    each boundary) cuts ``epochs * ceil(S/w)`` windows — a partial
    window round trip at EVERY epoch boundary — while the stitched arm
    materializes ONE multi-epoch schedule whose windows flow across the
    boundary, cutting only ``ceil(epochs*S/w)``. Both arms are
    prefetch-lane-bound (identical hit rates and consume lanes), so the
    boundary stall shows up directly in makespan: stitched must be
    STRICTLY below drain-and-refill (the guard), with retries == 0 on
    both (faults off).
    """
    # no smoke shrink: the arm is modeled (sub-second) and the boundary
    # margin needs all three epochs to be structural rather than thin
    del smoke
    # latency-bound: round trips dominate, so the extra boundary windows
    # and boundary demand misses are visible in makespan structurally
    net = InterconnectModel(latency_s=2e-3, bandwidth_Bps=100e9 / 8,
                            disk_bw_Bps=2.0e9)
    payload = bytes(file_size)
    paths = [f"bench/f_{i:06d}.bin" for i in range(num_files)]
    files = {p: payload for p in paths}
    blobs, _ = prepare_dataset(files, 8, compress=False)
    per_step = num_files // steps_per_epoch
    rng = np.random.default_rng(seed)
    epoch_steps: List[List[List[str]]] = []
    for _ in range(epochs):
        perm = [paths[int(i)] for i in rng.permutation(num_files)]
        epoch_steps.append([perm[s * per_step:(s + 1) * per_step]
                            for s in range(steps_per_epoch)])

    def build() -> FanStoreCluster:
        cluster = FanStoreCluster(2, interconnect=net,
                                  cache_bytes=cache_files * file_size,
                                  cache_policy="belady")
        cluster.load_partitions(blobs, replication=1)
        return cluster

    def run_stitched() -> Dict:
        cluster = build()
        flat = [b for ep in epoch_steps for b in ep]
        sched = EpochSchedule.from_trace({1: flat}, cluster)
        pf = PrefetchScheduler(cluster, sched, 1, window_steps=window)
        for gstep, batch in enumerate(flat):
            pf.ensure(gstep + window)
            pf.wait_ready(gstep)
            cluster.read_many(1, batch, materialize=False)
        pf.close()
        res = _cross_epoch_result(cluster, pf.windows_issued)
        cluster.close()
        return res

    def run_drain_refill() -> Dict:
        cluster = build()
        windows = 0
        for ep in epoch_steps:
            sched = EpochSchedule.from_trace({1: ep}, cluster)
            pf = PrefetchScheduler(cluster, sched, 1, window_steps=window)
            for s, batch in enumerate(ep):
                pf.ensure(s + window)
                pf.wait_ready(s)
                cluster.read_many(1, batch, materialize=False)
            pf.close()                  # the boundary stall: full drain,
            windows += pf.windows_issued  # then refill from scratch
        res = _cross_epoch_result(cluster, windows)
        cluster.close()
        return res

    stitched = run_stitched()
    drain = run_drain_refill()
    return {"epochs": epochs, "steps_per_epoch": steps_per_epoch,
            "window": window, "num_files": num_files,
            "cache_files": cache_files,
            "stitched": stitched, "drain_refill": drain,
            "stall_speedup": drain["makespan_s"] / stitched["makespan_s"]}


def _cross_epoch_result(cluster: FanStoreCluster, windows: int) -> Dict:
    clock = cluster.clocks[1]
    return {"makespan_s": cluster.makespan_s(),
            "cache_hit_rate": cluster.cache_hit_rate(),
            "prefetch_windows": windows,
            "prefetch_s": clock.prefetch_s,
            "consume_s": clock.consume_s,
            "retries": cluster.accounting.retries()}


def _drive_failover_epoch(cluster: FanStoreCluster,
                          traces: Dict[int, List[List[str]]], *,
                          victim: Optional[int] = None,
                          kill_step: Optional[int] = None
                          ) -> Tuple[int, List[int], Optional[str]]:
    """Drive one epoch step-by-step through the fault clock. A node in
    the failure set (or the designated victim once the kill step passes —
    a dead node stops issuing reads, it does not only stop serving) skips
    its batches. Returns (reads_failed, lost partition ids, error name);
    at R>=2 failover keeps reads_failed at zero, at R=1 the classified
    ``NodeLostError`` is caught and tallied here."""
    steps = max((len(s) for s in traces.values()), default=0)
    reads_failed = 0
    lost: List[int] = []
    error: Optional[str] = None
    for step in range(steps):
        cluster.tick_step(step)
        for nid, node_steps in sorted(traces.items()):
            if nid in cluster.failed or step >= len(node_steps):
                continue
            if (victim is not None and kill_step is not None
                    and nid == victim and step >= kill_step):
                continue
            try:
                cluster.read_many(nid, node_steps[step], materialize=False)
            except NodeLostError as e:
                reads_failed += len(node_steps[step])
                lost.extend(e.partitions)
                error = type(e).__name__
    return reads_failed, sorted(set(lost)), error


def failover_comparison(*, nodes: int = 8, smoke: bool = False,
                        kill_node: Optional[int] = None,
                        seed: int = 7) -> Dict:
    """Kill-a-node arm: the same trace driven over a healthy R=2 cluster
    and one whose FaultPolicy kills a node mid-epoch. The degraded run
    must finish every read via replica failover (zero client-visible
    errors), its retry ledger must equal the injector's raise count
    exactly, and its makespan stays within a small factor of healthy.
    The R=1 control shows the failure mode replication buys out of: the
    same kill surfaces as a classified ``NodeLostError`` naming the lost
    partitions — never a hang, never silent corruption."""
    file_size = 32 * 1024 if smoke else 256 * 1024
    reads_per_node = 96 if smoke else 128
    count = max(128, 2 * nodes)
    payload = bytes(np.random.default_rng(1).integers(
        0, 256, file_size, dtype=np.uint8))
    files = {f"bench/f_{i:06d}.bin": payload for i in range(count)}
    # enough partitions that every ring seat owns several — killing a
    # node must actually take data offline, not an empty seat
    blobs, _ = prepare_dataset(files, max(4 * nodes, 16), compress=False)
    paths = sorted(files)
    m = min(reads_per_node, count)
    steps = max(1, m // BATCH)
    kill_step = steps // 2
    if kill_node is None:
        # kill the most-loaded primary (ring placement is deterministic,
        # so this probe predicts every run below): the worst case, and a
        # guarantee the kill hits live traffic
        probe = FanStoreCluster.from_spec(ClusterSpec(
            num_nodes=nodes, replication=1, placement="ring"))
        probe.load_partitions(blobs, by_placement=True)
        victim = max(range(nodes),
                     key=lambda n: len(probe.nodes[n].partition_ids))
        probe.close()
    else:
        victim = kill_node

    rng = np.random.default_rng(nodes)
    traces: Dict[int, List[List[str]]] = {}
    for nid in range(nodes):
        chosen = [paths[int(i)]
                  for i in rng.choice(count, size=m, replace=False)]
        traces[nid] = [chosen[s:s + BATCH] for s in range(0, m, BATCH)]

    def run(replication: int, faults: Optional[Dict]) -> Dict:
        spec = ClusterSpec(num_nodes=nodes, replication=replication,
                           placement="ring", faults=faults)
        cluster = FanStoreCluster.from_spec(spec, interconnect=CPU_NET)
        cluster.load_partitions(blobs, by_placement=True)
        failed, lost, err = _drive_failover_epoch(
            cluster, traces,
            victim=victim if faults else None,
            kill_step=kill_step if faults else None)
        makespan = cluster.makespan_s()
        stats = cluster.fault_stats()
        healed = 0
        if faults and replication >= 2:
            # repair AFTER the epoch's makespan is captured: heal() ships
            # copies on the write lane, which is a separate story
            healed = cluster.heal()
        cluster.close()
        return {"makespan_s": makespan, "reads_failed": failed,
                "lost_partitions": lost, "error": err,
                "injected": stats["injected"], "retries": stats["retries"],
                "failed_nodes": stats["failed_nodes"],
                "healed_copies": healed}

    kill = {"kill_node": victim, "kill_at_step": kill_step, "seed": seed}
    healthy = run(2, None)
    degraded = run(2, kill)
    r1 = run(1, kill)
    return {"nodes": nodes, "steps": steps, "kill_node": victim,
            "kill_at_step": kill_step, "reads_per_node": m,
            "healthy": healthy, "degraded": degraded, "r1": r1,
            "degraded_ratio": (degraded["makespan_s"]
                               / healthy["makespan_s"])}


def format_failover_rows(fo: Dict) -> List[str]:
    d, r1 = fo["degraded"], fo["r1"]
    return [
        f"failover nodes={fo['nodes']} kill_node={fo['kill_node']} "
        f"kill_at_step={fo['kill_at_step']}/{fo['steps']}",
        f"  healthy  R=2 makespan={fo['healthy']['makespan_s'] * 1e3:.3f}ms",
        f"  degraded R=2 makespan={d['makespan_s'] * 1e3:.3f}ms "
        f"ratio={fo['degraded_ratio']:.2f}x reads_failed={d['reads_failed']} "
        f"injected={d['injected']} retries={d['retries']} "
        f"healed_copies={d['healed_copies']}",
        f"  control  R=1 error={r1['error']} "
        f"reads_failed={r1['reads_failed']} "
        f"lost_partitions={r1['lost_partitions']}",
    ]


def bench_json(*, nodes_list=(8, 64), smoke: bool = False) -> Dict:
    """Machine-readable perf snapshot: seed (per-file) / batched /
    prefetched arms at each node count, plus the cache-policy comparison.
    Written to BENCH_io.json by ``benchmarks/run.py --io-json`` so the perf
    trajectory is tracked from PR 2 on."""
    # reads span multiple BATCH-sized steps so a lookahead window has
    # batches to coalesce across (the whole point of the prefetch arm)
    file_size = 64 * 1024 if smoke else 512 * 1024
    reads_per_node = 96 if smoke else 128
    files_per_node = 16 if smoke else 32
    # small files: the latency/request-handling-bound regime where write
    # fan-in matters (the paper's many-small-files story, write side)
    write_size = 8 * 1024 if smoke else 16 * 1024
    # overlap arm: shard size comparable to the (halved) read phase, so
    # neither lane degenerates — when owner-side serve dominates BOTH
    # phases on the same node the overlap win collapses to ~0 by
    # construction (serve sums across lanes; that is the honest model)
    shard_bytes = (1 if smoke else 8) * 1024 * 1024
    overlap_reads = reads_per_node // 2
    window = 4
    results: Dict = {"config": {"file_size": file_size,
                                "reads_per_node": reads_per_node,
                                "batch": BATCH, "window": window,
                                "write_file_size": write_size,
                                "write_files_per_node": files_per_node,
                                "ckpt_shard_bytes": shard_bytes,
                                "smoke": smoke},
                     "arms": []}
    for nodes in nodes_list:
        count = max(128, 2 * nodes)
        kw = dict(file_size=file_size, count=count, net=CPU_NET,
                  reads_per_node=reads_per_node)
        seed_arm = run_one(nodes, batched=False, **kw)
        batched_arm = run_one(nodes, batched=True, **kw)
        prefetched_arm = run_one(nodes, prefetch=True, window=window,
                                 cache_policy="belady", **kw)
        entry = {"nodes": nodes, "count": count}
        for name, r in (("seed", seed_arm), ("batched", batched_arm),
                        ("prefetched", prefetched_arm)):
            entry[name] = {"makespan_s": r["makespan_s"],
                           "local_hit_rate": r["hit_rate"],
                           "cache_hit_rate": r["cache_hit_rate"],
                           "bytes_moved": r["bytes_moved"],
                           "prefetch_windows": r["prefetch_windows"]}
        entry["batched_speedup"] = (
            seed_arm["makespan_s"] / batched_arm["makespan_s"])
        entry["prefetch_speedup_vs_batched"] = (
            batched_arm["makespan_s"] / prefetched_arm["makespan_s"])
        # write half: batched write_many vs the per-file write_file loop,
        # plus checkpoint flush with/without prefetch-lane overlap
        wm = run_write_one(nodes, write_size, files_per_node, CPU_NET,
                           batched=True)
        wp = run_write_one(nodes, write_size, files_per_node, CPU_NET,
                           batched=False)
        ov = run_checkpoint_overlap(nodes, file_size, count, CPU_NET,
                                    reads_per_node=overlap_reads,
                                    window=window, shard_bytes=shard_bytes,
                                    chunk_bytes=max(shard_bytes // 4, 1))
        entry["write"] = {
            "write_many_makespan_s": wm["makespan_s"],
            "perfile_makespan_s": wp["makespan_s"],
            "write_speedup": wp["makespan_s"] / wm["makespan_s"],
            "write_rpcs": wm["write_rpcs"],
            "overlapped_makespan_s": ov["overlapped_makespan_s"],
            "serialized_makespan_s": ov["serialized_makespan_s"],
            "overlap_speedup": ov["overlap_speedup"]}
        results["arms"].append(entry)
    results["cache_policies"] = cache_policy_comparison()
    # the online-intelligence block: every registered policy x three byte
    # budgets x permutation + zipf traces (guarded: ARC/Predictive >= LRU
    # everywhere, Predictive closes >= 40% of the LRU->Belady zipf gap,
    # Belady upper bound, 2Q >= LRU on the scan arm)
    results["cache_policy_sweep"] = cache_policy_sweep(smoke=smoke)
    # cross-epoch prefetch stitching vs drain-and-refill (guarded:
    # stitched makespan strictly below, retries == 0 on both arms)
    results["cross_epoch"] = cross_epoch_comparison(smoke=smoke)
    # multi-tenant block: K co-located workers per node, shared cache
    # tier vs private per-worker budgets of the same total bytes
    results["workers"] = workers_comparison(smoke=smoke)
    # the hardware-truth block: the same trace over real wires (socket vs
    # shared memory), measured wall clocks — not modeled predictions.
    # Beside the read+write trace, the prefetch and checkpoint-overlap
    # benchmarks now carry their own measured arms with matching guards.
    results["measured"] = measured_comparison(smoke=smoke)
    results["measured"]["prefetch"] = measured_prefetch_comparison(
        smoke=smoke)
    results["measured"]["checkpoint"] = measured_ckpt_comparison(
        smoke=smoke)
    # the wire-gap block: single-conn vs striped/pipelined socket vs the
    # one-sided rdma backend on a pure-remote trace, plus wire-codec
    # engagement truth (cost-model-predicted only)
    results["measured"]["wire"] = measured_wire_comparison(smoke=smoke)
    # the prefetch-shape block: a slow latency-bound fabric with a deep
    # window — where the scheduler's win is structural, not a ~1% smoke
    # artifact (this is the guarded prefetch ratio)
    results["prefetch_depth"] = prefetch_depth_comparison(smoke=smoke)
    # the robustness block: kill a node mid-epoch at R=2 (every read must
    # finish via replica failover, retry ledger == injected faults,
    # bounded makespan inflation) with the R=1 classified-loss control
    results["failover"] = failover_comparison(smoke=smoke)
    # the serving-plane block: 64 read-mostly tenants on 8 nodes replaying
    # a zipfian shard trace through admission-gated tenant sessions —
    # hot-shard replication vs single-owner, per-tenant attribution
    # tie-out, and the inflight-byte cap (benchmarks/app_throughput.py;
    # smoke shrinks per-tenant request counts, never the tenant count)
    from benchmarks.app_throughput import serving_comparison
    results["serving"] = serving_comparison(smoke=smoke)
    return results


def main(*, batched: bool = False, prefetch: bool = False, window: int = 4,
         cache_mb: int = 0, epochs: Optional[int] = None,
         arms: Optional[List[str]] = None, write: bool = False,
         backend: str = "modeled", workers: int = 0,
         kill_node: bool = False) -> List[str]:
    if epochs is None:
        epochs = 2 if cache_mb else 1
    if kill_node:
        return format_failover_rows(failover_comparison())
    if workers:
        # shared node tier vs private per-worker caches, modeled, at a
        # few node counts (same total bytes either way)
        rows = []
        for n in (4, 8, 16):
            rows.append(run_workers_one(n, workers, 256 * 1024,
                                        max(128, 2 * n), CPU_NET,
                                        shared=True))
            rows.append(run_workers_one(n, workers, 256 * 1024,
                                        max(128, 2 * n), CPU_NET,
                                        shared=False))
        return format_workers_rows(rows)
    if backend != "modeled":
        # real wires: every node is an actual serving loop on this host,
        # so the measured axis sweeps small node counts only
        rows = [run_measured_one(backend, nodes=n) for n in (1, 2, 4, 8)]
        return format_measured_rows(rows)
    out = []
    for arm, fig in (("gpu", "fig5"), ("cpu", "fig6")):
        if arms and arm not in arms:
            continue
        if write:
            out.extend(format_write_rows(arm, run_write(arm)))
            continue
        rows = run(arm, batched=batched, prefetch=prefetch, window=window,
                   cache_mb=cache_mb, epochs=epochs)
        out.extend(format_rows(arm, fig, rows))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batched", action="store_true",
                    help="read through read_many (coalesced round trips) and "
                         "report the makespan win over the per-file path")
    ap.add_argument("--prefetch", action="store_true",
                    help="clairvoyant window prefetch (EpochSchedule + "
                         "PrefetchScheduler + Belady cache) and report the "
                         "makespan win over the batched path")
    ap.add_argument("--window", type=int, default=4,
                    help="prefetch lookahead window in training steps")
    ap.add_argument("--cache-mb", type=int, default=0,
                    help="per-node client read cache budget in MiB")
    ap.add_argument("--epochs", type=int, default=None,
                    help="read passes per node (default 1; 2 when caching)")
    ap.add_argument("--arm", choices=["gpu", "cpu"], default=None,
                    help="run a single arm instead of both")
    ap.add_argument("--write", action="store_true",
                    help="write-path scaling: batched write_many (one round "
                         "trip per (writer, owner) pair, write lane) vs the "
                         "per-file write_file loop")
    ap.add_argument("--backend", choices=["modeled", "socket", "shm", "rdma"],
                    default="modeled",
                    help="transport backend: 'modeled' runs the paper-scale "
                         "modeled sweeps; 'socket'/'shm'/'rdma' drive a real "
                         "wire and report MEASURED wall-clock makespans")
    ap.add_argument("--workers", type=int, default=0, metavar="K",
                    help="K co-located workers per node: shared node "
                         "cache tier vs private per-worker caches at the "
                         "same total byte budget (hit rate + makespan)")
    ap.add_argument("--kill-node", action="store_true",
                    help="fault-tolerance arm: kill one node mid-epoch at "
                         "R=2 (reads must all finish via replica failover) "
                         "vs the R=1 control (classified NodeLostError)")
    args = ap.parse_args()
    for line in main(batched=args.batched, prefetch=args.prefetch,
                     window=args.window, cache_mb=args.cache_mb,
                     epochs=args.epochs,
                     arms=[args.arm] if args.arm else None,
                     write=args.write, backend=args.backend,
                     workers=args.workers, kill_node=args.kill_node):
        print(line)
