"""Layered I/O engine: batched transport accounting, client LRU cache,
replica failover under read_many, async futures, FS commit regression."""
import random

import numpy as np
import pytest

from repro.data.pipeline import PrefetchLoader
from repro.data.sampler import GlobalUniformSampler
from repro.fanstore.cache import ByteLRUCache
from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.fs import FanStoreFS
from repro.fanstore.prepare import prepare_dataset


def make_cluster(num_nodes, files, *, replication=1, partitions=4, **kw):
    blobs, _ = prepare_dataset(files, partitions, compress=False)
    cluster = FanStoreCluster(num_nodes, **kw)
    cluster.load_partitions(blobs, replication=replication)
    return cluster


# ---- batched transport accounting -----------------------------------------

def test_read_many_single_owner_single_latency():
    """A batch of K files from one owner accrues exactly one latency_s."""
    files = {f"d/f{i}.bin": b"z" * 1000 for i in range(16)}
    cluster = make_cluster(2, files, partitions=1)   # everything on node 0
    cluster.reset_clocks()
    out = cluster.read_many(1, sorted(files))
    assert out == [files[p] for p in sorted(files)]
    net = cluster.net
    stored = 16 * 1000
    expect = net.latency_s + stored / net.bandwidth_Bps
    assert abs(cluster.clocks[1].consume_s - expect) < 1e-12
    # the owner handles ONE request message, not 16
    expect_serve = (net.open_overhead_s + stored / net.disk_bw_Bps
                    + stored / net.bandwidth_Bps)
    assert abs(cluster.clocks[0].serve_s - expect_serve) < 1e-12


def test_read_many_perfile_matches_read():
    """batched=False accrues byte-for-byte what N seed-style read calls do."""
    files = {f"d/f{i}.bin": b"q" * 500 for i in range(12)}
    a = make_cluster(3, files)
    b = make_cluster(3, files)
    a.reset_clocks()
    b.reset_clocks()
    for p in sorted(files):
        a.read(2, p)
    b.read_many(2, sorted(files), batched=False)
    for nid in range(3):
        assert abs(a.clocks[nid].consume_s - b.clocks[nid].consume_s) < 1e-12
        assert abs(a.clocks[nid].serve_s - b.clocks[nid].serve_s) < 1e-12
        assert a.clocks[nid].bytes_in == b.clocks[nid].bytes_in


def test_read_many_batched_strictly_cheaper_than_perfile():
    files = {f"d/f{i}.bin": b"z" * 2048 for i in range(64)}
    a = make_cluster(8, files, partitions=8)
    b = make_cluster(8, files, partitions=8)
    a.reset_clocks(), b.reset_clocks()
    a.read_many(0, sorted(files), batched=True)
    b.read_many(0, sorted(files), batched=False)
    assert a.makespan_s() < b.makespan_s()


def test_read_many_preserves_order_and_mixed_sources():
    files = {f"d/f{i}.bin": bytes([i]) * 100 for i in range(20)}
    cluster = make_cluster(4, files, replication=2, partitions=8)
    cluster.write_file(0, "out/w.bin", b"W" * 64)
    paths = sorted(files) + ["out/w.bin"]
    out = cluster.read_many(1, paths)
    assert out[:-1] == [files[p] for p in sorted(files)]
    assert out[-1] == b"W" * 64


def test_io_scaling_benchmark_batched_makespan_win_at_8_nodes():
    """Acceptance pin: the --batched benchmark path reports strictly lower
    makespan than the per-file path at >= 8 nodes."""
    import pathlib
    import sys
    root = str(pathlib.Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.io_scaling import CPU_NET, run_one
    kw = dict(nodes=8, file_size=8192, count=64, net=CPU_NET,
              reads_per_node=32)
    per_file = run_one(batched=False, **kw)
    batched = run_one(batched=True, **kw)
    assert batched["makespan_s"] < per_file["makespan_s"]


# ---- failover + replica selection under read_many --------------------------

def test_read_many_failover_with_replication():
    files = {f"d/f{i}.bin": bytes([i % 250]) * 300 for i in range(40)}
    cluster = make_cluster(4, files, replication=2, partitions=8)
    cluster.fail_node(2)
    out = cluster.read_many(0, sorted(files))
    assert out == [files[p] for p in sorted(files)]
    assert cluster.clocks[2].serve_s == 0.0          # failed node never serves
    with pytest.raises(IOError):
        cluster.read_many(2, sorted(files)[:1])      # failed requester


def test_read_many_least_loaded_spreads_across_replicas():
    files = {f"d/f{i}.bin": b"z" * 1000 for i in range(64)}
    cluster = make_cluster(4, files, replication=2, partitions=8)
    cluster.reset_clocks()
    cluster.read_many(3, sorted(files))              # node 3 reads everything
    # replica sets are {0,2} and {1,3}-style pairs; remote traffic must not
    # pile onto a single owner
    serving = [cluster.clocks[n].serve_s for n in range(3)]
    busy = [s for s in serving if s > 0]
    assert len(busy) >= 2
    assert max(busy) < 2.0 * min(busy) + 1e-9


def test_read_many_all_replicas_failed():
    files = {f"d/f{i}.bin": b"z" * 100 for i in range(8)}
    # 2 partitions round-robin onto nodes 0 and 1; node 2 owns nothing
    cluster = make_cluster(3, files, replication=1, partitions=2)
    cluster.fail_node(0)
    cluster.fail_node(1)
    with pytest.raises(IOError):
        cluster.read_many(2, sorted(files))


# ---- client-side LRU cache --------------------------------------------------

def test_lru_cache_hit_miss_eviction_accounting():
    cache = ByteLRUCache(250)
    assert cache.get("a") is None                    # miss on empty
    cache.put("a", b"x" * 100)
    cache.put("b", b"y" * 100)
    assert cache.get("a").data == b"x" * 100         # a is now MRU
    cache.put("c", b"z" * 100)                       # evicts b (LRU)
    assert cache.get("b") is None
    assert cache.get("a") is not None
    assert cache.stats.evictions == 1
    assert cache.stats.evicted_bytes == 100
    assert cache.used_bytes == 200
    assert 0 < cache.stats.hit_rate < 1
    # payloads over the whole budget are not cached
    assert cache.put("huge", b"h" * 1000) == 0
    assert "huge" not in cache


def test_cluster_cache_hits_on_second_epoch():
    files = {f"d/f{i}.bin": b"z" * 1000 for i in range(16)}
    cluster = make_cluster(2, files, partitions=1, cache_bytes=1 << 20)
    cluster.reset_clocks()
    first = cluster.read_many(1, sorted(files))
    second = cluster.read_many(1, sorted(files))
    assert first == second == [files[p] for p in sorted(files)]
    clock = cluster.clocks[1]
    assert clock.cache_misses == 16 and clock.cache_hits == 16
    assert clock.cache_hit_bytes == 16 * 1000
    assert cluster.cache_hit_rate() == 0.5
    # a cache hit must be modeled cheaper than the remote fetch it replaced
    hit_cost = cluster.net.cache_cost(1000)
    assert hit_cost < cluster.net.remote_cost(1000)


def test_cluster_cache_eviction_accounting_with_small_budget():
    files = {f"d/f{i}.bin": b"z" * 1000 for i in range(16)}
    cluster = make_cluster(2, files, partitions=1,
                           cache_bytes=3500)         # holds 3 files
    cluster.read_many(1, sorted(files))
    clock = cluster.clocks[1]
    assert clock.cache_evictions == 13               # 16 inserts, 3 resident
    assert cluster.caches[1].used_bytes <= 3500


def test_cache_size_only_entries_materialize_false():
    files = {f"d/f{i}.bin": b"z" * 1000 for i in range(4)}
    cluster = make_cluster(2, files, partitions=1, cache_bytes=1 << 20)
    cluster.read_many(1, sorted(files), materialize=False)
    cluster.read_many(1, sorted(files), materialize=False)
    assert cluster.clocks[1].cache_hits == 4         # placeholders hit
    # a materializing read must NOT serve payloads from size-only entries
    out = cluster.read_many(1, sorted(files))
    assert out == [files[p] for p in sorted(files)]


# ---- async future API -------------------------------------------------------

def test_read_many_async_returns_future():
    files = {f"d/f{i}.bin": bytes([i]) * 200 for i in range(10)}
    cluster = make_cluster(3, files)
    fut = cluster.read_many_async(0, sorted(files))
    assert fut.result(timeout=30) == [files[p] for p in sorted(files)]
    cluster.transport.shutdown()


def test_prefetch_loader_batched_path():
    """A batched loader hands its decode what ``read_many`` returns:
    read-only bytes-like payloads (here, with the tier off, views of the
    partition blobs), each equal to its file."""
    files = {f"d/f{i:03d}.bin": bytes([i]) * 64 for i in range(32)}
    cluster = make_cluster(2, files)
    paths = sorted(files)
    sampler = GlobalUniformSampler(len(paths), 8, seed=0)
    asked = []

    def fetch_many(idxs):
        asked.extend(paths[i] for i in idxs)
        return cluster.read_many(0, [paths[i] for i in idxs])

    loader = PrefetchLoader(sampler, fetch_many=fetch_many,
                            decode=lambda bl: bl)
    seen = []
    for batch in loader.batches(4):
        assert len(batch) == 8
        seen.extend(batch)
    assert len(seen) == 32 and len(asked) >= len(seen)
    for b, path in zip(seen, asked):
        assert memoryview(b).readonly and len(b) == 64
        assert bytes(b) == files[path]
    assert any(isinstance(b, memoryview) for b in seen)
    with pytest.raises(ValueError):
        PrefetchLoader(sampler, decode=lambda b: b)  # no fetch at all


def test_prefetch_loader_decode_packs_views_into_a_reused_buffer():
    """A decode that packs each batch into a reused host buffer by slice
    assignment, as a consumer staging batches for a device does, takes
    the views ``read_many`` hands out as it took ``bytes``: the packed
    batch equals its files, byte for byte."""
    rng = random.Random(7)
    files = {f"d/f{i:03d}.bin": rng.randbytes(40 + 37 * i)
             for i in range(32)}
    cluster = make_cluster(2, files)
    paths = sorted(files)
    sampler = GlobalUniformSampler(len(paths), 8, seed=3)
    asked = []
    ring = [np.zeros(sum(map(len, files.values())), np.uint8)
            for _ in range(2)]
    kinds = []

    def fetch_many(idxs):
        asked.append([paths[i] for i in idxs])
        return cluster.read_many(1, asked[-1])

    def decode(blobs):
        kinds.extend(type(b) for b in blobs)
        buf = ring[len(kinds) // 8 % len(ring)]
        view, off = memoryview(buf), 0
        for b in blobs:
            view[off:off + len(b)] = b
            off += len(b)
        return buf[:off].tobytes()     # the buffer is packed again later

    loader = PrefetchLoader(sampler, fetch_many=fetch_many, decode=decode,
                            depth=2)
    packed = list(loader.batches(8))
    assert len(packed) == 8 and len(asked) >= 8
    for got, batch in zip(packed, asked):
        assert got == b"".join(files[p] for p in batch)
    assert memoryview in kinds


# ---- FS layer commit regression --------------------------------------------

def test_fs_double_create_raises_via_close():
    """Regression: FanStoreFile.close() used to bypass write_file's
    single-write check and the metadata-forward accounting."""
    files = {"d/in.bin": b"i" * 100}
    cluster = make_cluster(2, files, partitions=1)
    fs = FanStoreFS(cluster, node_id=0)
    with fs.open("/fanstore/out/gen.bin", "wb") as f:
        f.write(b"first")
    assert cluster.read(1, "out/gen.bin") == b"first"
    f2 = fs.open("/fanstore/out/gen.bin", "wb")
    f2.write(b"second")
    with pytest.raises(PermissionError):
        f2.close()
    # the losing writer must not have clobbered the committed payload
    assert cluster.read(1, "out/gen.bin") == b"first"


def test_fs_close_accounts_metadata_forward():
    files = {"d/in.bin": b"i" * 100}
    cluster = make_cluster(4, files, partitions=1)
    fs = FanStoreFS(cluster, node_id=1)
    cluster.reset_clocks()
    with fs.open("/fanstore/out/acct.bin", "wb") as f:
        f.write(b"x" * 512)
    # committing through the FS layer accrues the same modeled time as
    # cluster.write_file: payload flush + (possibly) a metadata forward
    assert cluster.clocks[1].consume_s > 0.0
    other = FanStoreCluster(4)
    other.load_partitions(
        prepare_dataset(files, 1, compress=False)[0], replication=1)
    other.reset_clocks()
    other.write_file(1, "out/acct.bin", b"x" * 512)
    assert abs(cluster.clocks[1].consume_s - other.clocks[1].consume_s) < 1e-12
