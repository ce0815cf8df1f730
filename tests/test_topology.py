"""Topology-first runtime API: ClusterSpec, per-worker sessions, the
shared node cache tier, multi-requester schedules, and the cross-process
ShmArena attach path."""
import hashlib
import json
import multiprocessing
import threading

import numpy as np
import pytest

from repro.data.synthetic import small_file_dataset
from repro.fanstore.backends.shm import ShmArena, attach_and_digest
from repro.fanstore.cache import NodeCacheTier
from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.prefetch import (EpochSchedule, PrefetchScheduler,
                                     SchedulerGroup)
from repro.fanstore.prepare import prepare_dataset
from repro.fanstore.spec import ClusterSpec, WorkerContext


def _make_files(n=48, seed=3):
    files = small_file_dataset(n, (200, 1_500), num_dirs=3, seed=seed)
    blobs, _ = prepare_dataset(files, 8, compress=False)
    return files, blobs


# ---------------------------------------------------------------------------
# ClusterSpec: validation, suggestions, serialization
# ---------------------------------------------------------------------------

def test_spec_unknown_backend_fails_at_construction():
    with pytest.raises(ValueError, match=r"backend.*socket"):
        ClusterSpec(num_nodes=2, backend="sockets")


def test_spec_unknown_cache_policy_fails_at_construction():
    # regression: this used to surface only when the registry was hit,
    # deep inside cluster construction — now the spec names the choices
    with pytest.raises(ValueError, match=r"belady"):
        ClusterSpec(num_nodes=2, cache_policy="baledy")
    with pytest.raises(ValueError, match=r"lru"):
        ClusterSpec(num_nodes=2, cache_policy="nope")


def test_spec_unknown_placement_selector_scope_codec():
    with pytest.raises(ValueError, match=r"ring"):
        ClusterSpec(num_nodes=2, placement="rng")
    with pytest.raises(ValueError, match=r"least-loaded"):
        ClusterSpec(num_nodes=2, selector="least_loaded")
    with pytest.raises(ValueError, match=r"node.*worker|worker.*node"):
        ClusterSpec(num_nodes=2, cache_scope="shared")
    with pytest.raises(ValueError, match=r"lzss"):
        ClusterSpec(num_nodes=2, codec="lzs")


def test_spec_bounds():
    with pytest.raises(ValueError):
        ClusterSpec(num_nodes=0)
    with pytest.raises(ValueError):
        ClusterSpec(num_nodes=2, workers_per_node=0)
    with pytest.raises(ValueError):
        ClusterSpec(num_nodes=2, replication=3)
    with pytest.raises(ValueError):
        ClusterSpec(num_nodes=2, cache_bytes=-1)
    with pytest.raises(ValueError, match=r"interconnect.*latency_s"):
        ClusterSpec(num_nodes=2, interconnect={"latency": 1e-6})


def test_legacy_kwargs_raise_with_suggestions():
    # unknown names must not be silently swallowed; the message suggests
    with pytest.raises(TypeError, match=r"cache_policy"):
        FanStoreCluster(2, cache_polcy="lru")
    with pytest.raises(TypeError, match=r"backend"):
        FanStoreCluster(2, backnd="shm")
    # bad registry VALUES through the legacy path also fail up front
    with pytest.raises(ValueError, match=r"modeled.*shm.*socket|socket"):
        FanStoreCluster(2, backend="tcp")
    with pytest.raises(ValueError, match=r"2q"):
        FanStoreCluster(2, cache_policy="3q", cache_bytes=1024)


def test_spec_json_round_trip_is_identity():
    spec = ClusterSpec(num_nodes=8, workers_per_node=2, backend="shm",
                       cache_policy="belady", cache_bytes=123456,
                       cache_scope="worker", placement="ring",
                       selector="power-of-two", replication=2,
                       io_threads=3,
                       interconnect={"latency_s": 2e-6},
                       backend_options={})
    again = ClusterSpec.from_json(spec.to_json())
    assert again == spec
    assert again.to_json() == spec.to_json()
    # and the dict form rejects unknown fields with suggestions
    d = json.loads(spec.to_json())
    d["num_node"] = 4
    with pytest.raises(ValueError, match=r"num_nodes"):
        ClusterSpec.from_dict(d)


def test_spec_workers_enumeration_and_budget_split():
    spec = ClusterSpec(num_nodes=2, workers_per_node=2, cache_bytes=1000,
                       cache_scope="worker")
    assert [c.key for c in spec.workers()] == [(0, 0), (0, 1),
                                               (1, 0), (1, 1)]
    assert spec.total_workers == 4
    assert spec.worker_cache_bytes() == 500
    assert spec.replace(workers_per_node=1).workers_per_node == 1
    # the tier's private split and the spec helper must agree (one
    # contract, two layers — this pins them together)
    tier = FanStoreCluster.from_spec(spec).cache_tiers[0]
    assert all(c.capacity_bytes == spec.worker_cache_bytes()
               for c in tier.member_caches())


def test_from_spec_equals_legacy_modeled_clocks():
    """Topology/constructor-independence pin: the same trace through a
    spec-built and a legacy-kwargs-built cluster accrues identical
    modeled clocks (single-worker, the pre-topology contract)."""
    files, blobs = _make_files()
    paths = sorted(files)[:24]

    def drive(cluster):
        cluster.load_partitions(blobs, replication=2)
        for nid in range(4):
            cluster.read_many(nid, paths)
        return [(c.consume_s, c.serve_s, c.bytes_in, c.local_bytes)
                for c in cluster.clocks.values()]

    legacy = drive(FanStoreCluster(4, cache_bytes=4096, cache_policy="lru"))
    spec = ClusterSpec(num_nodes=4, cache_bytes=4096, cache_policy="lru")
    via_spec = drive(FanStoreCluster.from_spec(spec))
    assert legacy == via_spec


def test_modeled_costs_worker_independent():
    """Modeled quantities must not depend on WHICH worker read — only
    the attribution breakdown does (by contract, like backends)."""
    files, blobs = _make_files()
    paths = sorted(files)[:16]
    spec = ClusterSpec(num_nodes=2, workers_per_node=2, cache_bytes=1 << 20)

    def drive(worker_id):
        c = FanStoreCluster.from_spec(spec)
        c.load_partitions(blobs)
        c.read_many(0, paths, worker_id=worker_id)
        clock = c.clocks[0]
        return (clock.consume_s, clock.bytes_in, clock.local_bytes,
                clock.cache_hits, clock.cache_misses)

    assert drive(0) == drive(1)


# ---------------------------------------------------------------------------
# connect() / WorkerContext / sessions
# ---------------------------------------------------------------------------

def test_connect_bounds_and_context():
    spec = ClusterSpec(num_nodes=2, workers_per_node=2)
    cluster = FanStoreCluster.from_spec(spec)
    sess = cluster.connect(1, 1)
    assert sess.context == WorkerContext(1, 1)
    assert sess.context.key == (1, 1)
    with pytest.raises(ValueError, match=r"node_id 5"):
        cluster.connect(5)
    with pytest.raises(ValueError, match=r"workers_per_node"):
        cluster.connect(0, worker_id=2)
    # direct session construction rejects the same coordinates (it used
    # to fail late on the first cached read, or silently with no cache)
    from repro.fanstore.api import FanStoreSession
    with pytest.raises(ValueError, match=r"workers_per_node"):
        FanStoreSession(cluster, 0, worker_id=5)
    with pytest.raises(ValueError):
        WorkerContext(-1, 0)


def test_colocated_sessions_share_node_tier():
    """A payload fetched by worker 0 is a RAM hit for worker 1 on the
    same node — the Hoard shared-tier behavior sessions now get."""
    files, blobs = _make_files()
    spec = ClusterSpec(num_nodes=2, workers_per_node=2,
                       cache_bytes=1 << 20)
    cluster = FanStoreCluster.from_spec(spec)
    cluster.load_partitions(blobs)
    s0, s1 = cluster.connect(0, 0), cluster.connect(0, 1)
    paths = sorted(files)[:12]
    assert s0.read_many(paths) == [files[p] for p in paths]
    before = cluster.clocks[0].cache_hits
    assert s1.read_many(paths) == [files[p] for p in paths]
    tier = cluster.cache_tiers[0]
    assert cluster.clocks[0].cache_hits == before + len(paths)
    # attribution: worker 1's hits are credited to worker 1
    assert tier.worker_stats[1].hits == len(paths)
    assert cluster.clocks[0].worker_cache_hits.get(1, 0) == len(paths)
    # worker 0 only warmed (misses), never hit
    assert tier.worker_stats[0].hits == 0


def test_private_scope_does_not_share():
    files, blobs = _make_files()
    spec = ClusterSpec(num_nodes=2, workers_per_node=2,
                       cache_bytes=1 << 20, cache_scope="worker")
    cluster = FanStoreCluster.from_spec(spec)
    cluster.load_partitions(blobs)
    paths = sorted(files)[:12]
    cluster.connect(0, 0).read_many(paths)
    cluster.connect(0, 1).read_many(paths)
    tier = cluster.cache_tiers[0]
    assert tier.worker_stats[0].hits == tier.worker_stats[1].hits == 0
    # each private split holds its own copy; the shared tier would hold one
    caches = tier.member_caches()
    assert len(caches) == 2 and caches[0] is not caches[1]


def test_attribution_sums_match_tier_totals_concurrent():
    """Concurrent co-located sessions: per-worker attribution sums equal
    the tier totals AND the NodeClock mirror — no double-accounting under
    the serving/pool thread interleave (thread-leak fixture guards the
    teardown)."""
    files, blobs = _make_files(n=64)
    spec = ClusterSpec(num_nodes=2, workers_per_node=4,
                       cache_bytes=2 << 20)
    with FanStoreCluster.from_spec(spec) as cluster:
        cluster.load_partitions(blobs)
        paths = sorted(files)
        errs = []

        def worker(w):
            try:
                sess = cluster.connect(0, w)
                rng = np.random.default_rng(w)
                for _ in range(4):
                    chosen = [paths[int(i)] for i in
                              rng.integers(0, len(paths), size=16)]
                    got = sess.read_many(chosen)
                    assert got == [files[p] for p in chosen]
            except BaseException as e:   # surfaces after join
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        tier = cluster.cache_tiers[0]
        clock = cluster.clocks[0]
        hits = sum(s.hits for s in tier.worker_stats.values())
        misses = sum(s.misses for s in tier.worker_stats.values())
        assert hits == tier.stats.hits == clock.cache_hits
        assert misses == tier.stats.misses == clock.cache_misses
        assert sum(clock.worker_cache_hits.values()) == clock.cache_hits
        assert sum(clock.worker_cache_misses.values()) == clock.cache_misses
        assert hits + misses == 2 * 4 * 4 * 16 // 2  # 4 workers x 4 x 16


def test_legacy_caches_view_still_works():
    files, blobs = _make_files()
    cluster = FanStoreCluster(2, cache_bytes=1 << 20)
    cluster.load_partitions(blobs)
    paths = sorted(files)[:6]
    cluster.read_many(1, paths)
    assert paths[0] in cluster.caches[1]
    assert cluster.caches[1].used_bytes > 0
    assert isinstance(cluster.cache_tiers[1], NodeCacheTier)


# ---------------------------------------------------------------------------
# Shared tier beats private budgets (the acceptance pin) + benchmarks
# ---------------------------------------------------------------------------

def test_shared_tier_beats_private_at_8x2():
    """8 nodes x 2 workers: the shared node tier strictly beats private
    per-worker caches of the SAME total bytes on both hit rate and
    modeled makespan (deterministic modeled quantities)."""
    from benchmarks.io_scaling import CPU_NET, run_workers_one
    kw = dict(file_size=64 * 1024, count=128, net=CPU_NET,
              reads_per_worker=32, epochs=2)
    shared = run_workers_one(8, 2, shared=True, **kw)
    private = run_workers_one(8, 2, shared=False, **kw)
    assert shared["budget_bytes"] == private["budget_bytes"]
    assert shared["cache_hit_rate"] > private["cache_hit_rate"]
    assert shared["makespan_s"] < private["makespan_s"]
    assert shared["attribution_ok"] and private["attribution_ok"]


def test_workers_comparison_block_shape():
    from benchmarks.io_scaling import workers_comparison
    block = workers_comparison(nodes=4, workers=2, smoke=True)
    assert block["shared_speedup"] > 1.0
    assert block["hit_rate_gain"] > 0
    assert block["shared"]["cache_scope"] == "node"
    assert block["private"]["cache_scope"] == "worker"


# ---------------------------------------------------------------------------
# Per-(node, worker) schedules and the multi-requester driver path
# ---------------------------------------------------------------------------

class _PeekableSampler:
    """Minimal sampler: fixed epoch permutation, peek_epoch only."""

    def __init__(self, n, batch, seed=0):
        self.n, self.batch, self.seed = n, batch, seed

    def peek_epoch(self, epoch=None):
        perm = np.random.default_rng(self.seed).permutation(self.n)
        return [perm[i:i + self.batch]
                for i in range(0, self.n - self.batch + 1, self.batch)]


def test_epoch_schedule_worker_axis_slicing():
    files, blobs = _make_files(n=32)
    paths = sorted(files)
    spec = ClusterSpec(num_nodes=2, workers_per_node=2)
    cluster = FanStoreCluster.from_spec(spec)
    cluster.load_partitions(blobs)
    sampler = _PeekableSampler(32, 8)
    sched = EpochSchedule.from_sampler(sampler, paths, num_requesters=4,
                                       workers_per_node=2, cluster=cluster)
    assert sched.requesters == [(0, 0), (0, 1), (1, 0), (1, 1)]
    # slices are contiguous node-major: requester (n, w) takes slice
    # index n*W + w of each batch (flat comparison built without a
    # cluster — slice indices are not node ids there)
    flat = EpochSchedule.from_sampler(sampler, paths, num_requesters=4)
    for r in range(4):
        key = (r // 2, r % 2)
        assert [s.path for s in sched.for_requester(key)] == \
            [s.path for s in flat.for_requester(r)]
    # node_future merges both workers per step, worker-stable
    merged = sched.node_future(0)
    per_step = len(merged) // sched.num_steps
    w0 = sched.future_paths((0, 0))
    w1 = sched.future_paths((0, 1))
    assert merged[:per_step] == w0[:per_step // 2] + w1[:per_step // 2]
    assert sorted(merged) == sorted(w0 + w1)


def test_scheduler_group_drives_all_workers():
    files, blobs = _make_files(n=64)
    paths = sorted(files)
    spec = ClusterSpec(num_nodes=2, workers_per_node=2,
                       cache_bytes=2 << 20, cache_policy="belady")
    with FanStoreCluster.from_spec(spec) as cluster:
        cluster.load_partitions(blobs)
        traces = {}
        rng = np.random.default_rng(5)
        for n in range(2):
            for w in range(2):
                chosen = [paths[int(i)] for i in rng.choice(
                    len(paths), size=16, replace=False)]
                traces[(n, w)] = [chosen[s:s + 4]
                                  for s in range(0, 16, 4)]
        sched = EpochSchedule.from_trace(traces, cluster)
        group = SchedulerGroup.for_schedule(cluster, sched, window_steps=2)
        assert len(group) == 4
        for step in range(4):
            group.ensure(step + 2)
            group.wait_ready(step)
            for (n, w), steps in traces.items():
                got = cluster.read_many(n, steps[step], worker_id=w)
                assert got == [files[p] for p in steps[step]]
        group.close()
        # every (node, worker) demand read hit its prefetched tier entry
        for n in range(2):
            tier = cluster.cache_tiers[n]
            for w in range(2):
                assert tier.worker_stats[w].hits == 16
        # prefetch cost accrued on BOTH nodes: no node-0 pin
        assert all(cluster.clocks[n].prefetch_s > 0 for n in range(2))


def test_schedule_spread_beats_node0_pin():
    """Multi-requester scheduling: spreading the epoch across every
    (node, worker) yields a strictly lower modeled makespan than pinning
    all reads to node 0 (the old driver behavior)."""
    files, blobs = _make_files(n=64)
    paths = sorted(files)
    sampler = _PeekableSampler(64, 16)

    def run(requesters, workers_per_node):
        spec = ClusterSpec(num_nodes=4, workers_per_node=workers_per_node,
                           cache_bytes=4 << 20, cache_policy="belady")
        with FanStoreCluster.from_spec(spec) as cluster:
            cluster.load_partitions(blobs)
            sched = EpochSchedule.from_sampler(
                sampler, paths, num_requesters=requesters,
                workers_per_node=workers_per_node, cluster=cluster)
            group = SchedulerGroup.for_schedule(cluster, sched,
                                                window_steps=2)
            group.run_all()
            group.close()
            for r in sched.requesters:
                node = r[0] if isinstance(r, tuple) else r
                w = r[1] if isinstance(r, tuple) else 0
                for s in sched.for_requester(r):
                    cluster.read_many(node, [s.path], worker_id=w)
            return cluster.makespan_s()

    pinned = run(1, 1)           # whole epoch through node 0
    spread = run(8, 2)           # one loader per (node, worker)
    assert spread < pinned


def test_belady_future_installs_node_merged_through_tier():
    files, blobs = _make_files(n=32)
    paths = sorted(files)
    spec = ClusterSpec(num_nodes=2, workers_per_node=2,
                       cache_bytes=1 << 20, cache_policy="belady")
    cluster = FanStoreCluster.from_spec(spec)
    cluster.load_partitions(blobs)
    traces = {(0, w): [[p] for p in paths[w::2]] for w in range(2)}
    sched = EpochSchedule.from_trace(traces, cluster)
    fed = sched.install_futures(cluster)
    assert fed == 1                      # ONE shared cache per node fed once
    cache = cluster.cache_tiers[0].cache_for(0)
    assert cache is cluster.cache_tiers[0].cache_for(1)
    assert sum(len(q) for q in cache._future.values()) == len(paths)


# ---------------------------------------------------------------------------
# Cross-process ShmArena attach (spawn)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not ShmArena.available,
                    reason="multiprocessing.shared_memory unavailable")
def test_cross_process_shm_attach_round_trip():
    """The acceptance pin: a SPAWNED process rebuilds the ClusterSpec
    from JSON and reads byte-identical payloads through attached
    ShmArena segments."""
    files, blobs = _make_files(n=10)
    spec = ClusterSpec(num_nodes=2, workers_per_node=2, backend="shm")
    with FanStoreCluster.from_spec(spec) as cluster:
        cluster.transport.arena = ShmArena()
        cluster.load_partitions(blobs)
        # outputs ride the same export path as inputs
        cluster.write_file(0, "out/extra.bin", b"spawned" * 100)
        handles = {}
        for owner in range(2):
            local = [p for p in files if cluster.nodes[owner].has(p)]
            handles.update(cluster.transport.export_paths(owner, local))
        out_owner = cluster.placement.owner("out/extra.bin")
        handles.update(cluster.transport.export_paths(
            out_owner, ["out/extra.bin"]))
        ctx = multiprocessing.get_context("spawn")
        with ctx.Pool(1) as pool:
            result = pool.apply(attach_and_digest,
                                (spec.to_json(), handles))
        # the child's re-serialized spec is the identity round trip
        assert result["spec_json"] == spec.to_json()
        assert result["workers_per_node"] == 2
        expected = dict(files)
        expected["out/extra.bin"] = b"spawned" * 100
        assert set(result["digests"]) == set(handles)
        for path, digest in result["digests"].items():
            assert digest == hashlib.sha256(expected[path]).hexdigest()
            assert result["sizes"][path] == len(expected[path])


@pytest.mark.skipif(not ShmArena.available,
                    reason="multiprocessing.shared_memory unavailable")
def test_export_paths_requires_arena():
    spec = ClusterSpec(num_nodes=1, backend="shm")
    with FanStoreCluster.from_spec(spec) as cluster:
        with pytest.raises(RuntimeError, match="arena"):
            cluster.transport.export_paths(0, ["x"])
