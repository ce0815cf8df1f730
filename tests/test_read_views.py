"""``read_many`` hands out read-only views of the partition blobs.

Where the one-pass gather engages (a batched, materialized read with the
requester's cache tier off), an input record stored raw comes back as a
read-only ``memoryview`` whose ``obj`` is its partition blob: from the
requester's own partitions on every wire, and from an owner's on the
modeled wire, which serves a peer by the owner's ``NodeStore.serve_many``.
The view cannot be written, and it stays valid and equal to the file
after its partition is dropped or its node is killed. ``fanstore.read_many``
counts the views it handed out in ``files_viewed``.

Every other read still returns owning ``bytes``: ``cluster.read``, the
session's descriptor reads, ``batched=False``, ``materialize=False``, an
enabled cache tier (scheduled prefetch included), LZSS records, committed
outputs, files a descriptor holds open, and the remote legs of the socket,
shm and rdma wires.
"""
import gc
import random

import pytest

from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.metrics import SPANS
from repro.fanstore.prepare import prepare_dataset
from repro.fanstore.spec import ClusterSpec

BACKENDS = ["modeled", "socket", "shm", "rdma"]
COPYING_WIRES = ["socket", "shm", "rdma"]


@pytest.fixture
def recording():
    """The process-wide recorder, forced on and emptied; restored after."""
    was = SPANS.forced
    SPANS.forced = True
    SPANS.clear()
    yield SPANS
    SPANS.forced = was
    SPANS.clear()


def make_files(n=40):
    rng = random.Random(17)
    return {f"train/c{i % 4}/f_{i:03d}.bin": rng.randbytes(200 + 53 * i)
            for i in range(n)}


def build(backend, files, *, compress=False, **spec_kw):
    blobs, _ = prepare_dataset(files, 8, compress=compress)
    c = FanStoreCluster.from_spec(
        ClusterSpec(num_nodes=4, backend=backend, **spec_kw))
    c.load_partitions(blobs)
    return c


def owner_blob(c, path, node):
    """The partition blob ``node`` holds ``path`` in."""
    return c.nodes[node].partition_blob(
        c.metadata.lookup(path)[1].partition_id)


def assert_view_of(c, d, path, node, want):
    assert type(d) is memoryview and d.readonly
    assert d.obj is owner_blob(c, path, node)
    assert d == want and bytes(d) == want


# ---------------------------------------------------------------------------
# views where the gather engages
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_gathered_raw_payloads_are_read_only_views(backend):
    files = make_files()
    paths = sorted(files)
    with build(backend, files) as c:
        for node in range(c.num_nodes):
            got = c.read_many(node, paths)
            remote = 0
            for path, d in zip(paths, got):
                if c.nodes[node].has(path):
                    assert_view_of(c, d, path, node, files[path])
                    continue
                remote += 1
                if backend == "modeled":
                    owner = c.metadata.lookup(path)[1].node_id
                    assert_view_of(c, d, path, owner, files[path])
                else:
                    assert type(d) is bytes and d == files[path]
            assert 0 < remote < len(paths)


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_view_cannot_be_written(backend):
    files = make_files()
    paths = sorted(files)
    with build(backend, files) as c:
        got = c.read_many(0, paths)
        views = [(p, d) for p, d in zip(paths, got)
                 if type(d) is memoryview]
        assert views
        for path, d in views:
            with pytest.raises(TypeError):
                d[0:1] = b"\x00"
            with pytest.raises(TypeError):
                d[:] = bytes(len(d))
            assert d == files[path]
        assert c.read_many(0, paths) == [files[p] for p in paths]


@pytest.mark.parametrize("backend", BACKENDS)
def test_views_outlive_their_partitions(backend):
    files = make_files()
    paths = sorted(files)
    with build(backend, files) as c:
        held = []
        for node in range(c.num_nodes):
            held.extend((p, d) for p, d in zip(paths, c.read_many(node, paths))
                        if type(d) is memoryview)
        assert held
        for store in c.nodes.values():
            for pid in store.partition_ids:
                store.drop_partition(pid)
            assert store.partition_ids == ()
    gc.collect()
    for path, d in held:
        assert d.readonly and d == files[path]


@pytest.mark.parametrize("backend", BACKENDS)
def test_views_survive_a_node_kill_at_r2(backend):
    """Node 2 is killed after five transport operations at R = 2: reads
    fail over to the surviving replica, still as views where the gather
    engages, and every view taken before or after the kill stays equal to
    its file once the dead node's partitions are gone."""
    files = make_files()
    paths = sorted(files)
    rng = random.Random(5)
    with build(backend, files, replication=2,
               faults={"kill_node": 2, "kill_at_op": 5, "seed": 5}) as c:
        held = []
        for rnd in range(3):
            c.tick_step(rnd)
            for node in range(c.num_nodes):
                if node in c.failed:
                    continue
                batch = rng.sample(paths, 20)
                got = c.read_many(node, batch)
                assert [bytes(d) for d in got] == [files[p] for p in batch]
                held.extend((p, d) for p, d in zip(batch, got)
                            if type(d) is memoryview)
        faults = c.fault_stats()
        assert faults["killed"] and faults["injected"] > 0
        dead = c.nodes[2]
        for pid in dead.partition_ids:
            dead.drop_partition(pid)
        after = c.read_many(0, paths)
        assert [bytes(d) for d in after] == [files[p] for p in paths]
        if backend == "modeled":
            assert all(type(d) is memoryview for d in after)
    assert held
    gc.collect()
    for path, d in held:
        assert d == files[path]


@pytest.mark.parametrize("backend", BACKENDS)
def test_files_viewed_ties_out_with_the_files_served(backend, recording):
    files = make_files()
    paths = sorted(files)
    with build(backend, files) as c:
        returned = []
        for node in range(c.num_nodes):
            got = c.read_many(node, paths)
            returned.append(sum(type(d) is memoryview for d in got))
    reads = recording.spans("fanstore.read_many")
    assert len(reads) == c.num_nodes
    for read, n in zip(reads, returned):
        k = read.counters
        assert k["files_viewed"] == n == k["files_local"] + (
            k["files_remote"] if backend == "modeled" else 0)
        assert k["files_viewed"] == k["files_gathered"]
    if backend == "modeled":
        assert all(r.counters["files_viewed"] == len(paths) for r in reads)


# ---------------------------------------------------------------------------
# owning bytes everywhere else
# ---------------------------------------------------------------------------
# each case reads on every node and returns (payloads that must be bytes,
# the files they must equal)

def _read(files, paths):
    with build("modeled", files) as c:
        got = [c.read(n, p) for n in range(c.num_nodes) for p in paths]
    return got, [files[p] for _ in range(4) for p in paths]


def _posix(files, paths):
    with build("modeled", files) as c:
        got = []
        for node in range(c.num_nodes):
            s = c.connect(node)
            for p in paths:
                fd = s.open(p)
                got.append(s.read(fd))
                s.close(fd)
    return got, [files[p] for _ in range(4) for p in paths]


def _unbatched(files, paths):
    with build("modeled", files) as c:
        got = [d for n in range(c.num_nodes)
               for d in c.read_many(n, paths, batched=False)]
    return got, [files[p] for _ in range(4) for p in paths]


def _nomat(files, paths):
    with build("modeled", files) as c:
        got = [d for n in range(c.num_nodes)
               for d in c.read_many(n, paths, materialize=False)]
    return got, [b""] * len(got)


def _tier(files, paths):
    with build("modeled", files, cache_bytes=1 << 20) as c:
        got = []
        for node in range(c.num_nodes):
            c.prefetch_window(node, paths[node::3])
            got.extend(c.read_many(node, paths))     # hits and misses
            got.extend(c.read_many(node, paths))     # every file a hit
    return got, [files[p] for _ in range(8) for p in paths]


def _lzss(files, paths):
    """Compressible files: every record is stored LZSS-packed."""
    rng = random.Random(3)
    words = [rng.randbytes(6) for _ in range(12)]
    packed = {p: b"".join(rng.choice(words) for _ in range(60 + 9 * i))
              for i, p in enumerate(paths)}
    with build("modeled", packed, compress=True) as c:
        assert all(node.read_record(p).blob is None
                   for node in c.nodes.values() for p in node.local_paths())
        got = [d for n in range(c.num_nodes) for d in c.read_many(n, paths)]
    return got, [packed[p] for _ in range(4) for p in paths]


def _outputs(files, paths):
    outs = {f"out/o_{k}.bin": bytes([k]) * (100 + 37 * k) for k in range(8)}
    names = sorted(outs)
    with build("modeled", files) as c:
        c.write_many(0, list(outs.items())[:4])
        c.write_many(3, list(outs.items())[4:])
        got = [d for n in range(c.num_nodes) for d in c.read_many(n, names)]
    return got, [outs[p] for _ in range(4) for p in names]


def _open(files, paths):
    """Files held open through each store's descriptors come from its
    refcount cache as ``bytes``; the batch's other files are views."""
    with build("modeled", files) as c:
        held = {p for node in c.nodes.values()
                for p in sorted(node.local_paths())[:4]}
        for node in c.nodes.values():
            for p in sorted(node.local_paths())[:4]:
                node.open_local(p)
        got, want = [], []
        for n in range(c.num_nodes):
            for p, d in zip(paths, c.read_many(n, paths)):
                if p in held:
                    got.append(d)
                    want.append(files[p])
                else:
                    assert type(d) is memoryview
    assert len(got) == 4 * len(held)
    return got, want


def _remote_leg(backend):
    def case(files, paths):
        with build(backend, files) as c:
            got, want = [], []
            for n in range(c.num_nodes):
                for p, d in zip(paths, c.read_many(n, paths)):
                    if not c.nodes[n].has(p):
                        got.append(d)
                        want.append(files[p])
        return got, want
    return case


BYTES_CASES = {
    "read": _read, "posix": _posix, "unbatched": _unbatched,
    "nomat": _nomat, "tier": _tier, "lzss": _lzss, "outputs": _outputs,
    "open": _open,
    **{f"{b}-remote": _remote_leg(b) for b in COPYING_WIRES},
}
# cases in which no file of a read is a view
NO_VIEWS = ("read", "posix", "unbatched", "nomat", "tier", "lzss", "outputs")


@pytest.mark.parametrize("case", sorted(BYTES_CASES))
def test_these_reads_return_owning_bytes(case, recording):
    files = make_files()
    got, want = BYTES_CASES[case](files, sorted(files))
    assert got and all(type(d) is bytes for d in got)
    assert got == want
    reads = recording.spans("fanstore.read_many")
    assert reads
    if case in NO_VIEWS:
        assert all(r.counters["files_viewed"] == 0 for r in reads)
