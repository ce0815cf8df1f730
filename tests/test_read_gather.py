"""The one-pass gather in ``read_many`` against the read path before it.

``read_many`` serves a batch in one pass where it can (a batched,
materialized read with the requester's cache tier off): each path is
resolved once and each uncompressed input record is copied straight from
its partition blob. Everything a caller or a ledger can see must come out
as it did before the gather: the payloads, every ``NodeClock`` field
(compared with ``==``), the measured wires' request, byte and stripe
ledgers, ``NodeStore.stats`` and the span counters. The expected values
in ``data/read_gather_pinned.json`` were recorded from the read path as
it was before the gather (commit f8b3eab); ``PYTHONPATH=<src> python
tests/test_read_gather.py --pin`` records them again from the engine
under ``<src>``.

Cases cover every backend with R = 1 and R = 2, raw and LZSS partitions,
the cache tier on (with scheduled prefetch), a batch mixing committed
outputs with inputs, files held open through a store's descriptors,
``batched=False``, ``materialize=False``, the serving lane, and a node
killed mid-batch at R = 2.
"""
import dataclasses
import hashlib
import json
import random
import sys
from pathlib import Path

import pytest

from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.metrics import SPANS
from repro.fanstore.prepare import prepare_dataset
from repro.fanstore.spec import ClusterSpec

PINNED = Path(__file__).parent / "data" / "read_gather_pinned.json"
BACKENDS = ["modeled", "socket", "shm", "rdma"]
VARIANTS = ["r1", "r2", "lzss", "tier", "outputs", "open", "unbatched",
            "nomat", "serve", "kill"]
CASES = [f"{b}-{v}" for b in BACKENDS for v in VARIANTS]
# counters that time something (they differ run to run), and the one the
# gather adds
UNPINNED = ("files_gathered",)


def make_files(n=48):
    """Compressible files of uneven sizes (LZSS packs every one)."""
    rng = random.Random(1234)
    files = {}
    for i in range(n):
        words = [bytes(rng.choice(b"abcdefgh") for _ in range(6))
                 for _ in range(12)]
        body = b"".join(rng.choice(words) for _ in range(40 + 23 * i))
        files[f"train/c{i % 4}/f_{i:03d}.bin"] = body
    return files


def _wall_counts(wall):
    """A ``WallClock`` without its nanosecond timings."""
    return {k: v for k, v in dataclasses.asdict(wall).items()
            if not k.endswith("_ns")}


def _counters(spans):
    """Per span name: how many, and each counter summed (timings and the
    gather's own counter left out)."""
    out = {}
    for s in spans:
        row = out.setdefault(s.name, {"count": 0})
        row["count"] += 1
        for k, v in s.counters.items():
            if not k.endswith("_ns") and k not in UNPINNED:
                row[k] = row.get(k, 0) + v
    return out


def run_case(case):
    """Drive one case's reads; returns its payload digest and ledgers,
    normalized through JSON."""
    backend, variant = case.split("-")
    files = make_files()
    blobs, _ = prepare_dataset(files, 8, compress=variant == "lzss")
    spec = {"num_nodes": 4, "backend": backend,
            "replication": 2 if variant in ("r2", "kill") else 1}
    if variant == "tier":
        spec["cache_bytes"] = 24 << 10   # small: inserts evict
    if variant == "kill":
        spec["faults"] = {"kill_node": 2, "kill_at_op": 5, "seed": 5}
    read_kw = {"unbatched": {"batched": False},
               "nomat": {"materialize": False},
               "serve": {"lane": "serve_app"}}.get(variant, {})
    paths = sorted(files)
    expect = dict(files)
    digest = hashlib.sha256()
    was = SPANS.forced
    SPANS.forced = True
    SPANS.clear()
    try:
        with FanStoreCluster.from_spec(ClusterSpec(**spec)) as c:
            c.load_partitions(blobs)
            if variant == "outputs":
                outs = {f"out/o_{k}.bin": bytes([k]) * (100 + 37 * k)
                        for k in range(8)}
                c.write_many(0, list(outs.items())[:4])
                c.write_many(3, list(outs.items())[4:])
                expect.update(outs)
                paths = sorted(expect)
            if variant == "open":
                # descriptors held open on each store: those reads are
                # served from the store's refcount cache
                for node in c.nodes.values():
                    for path in sorted(node.local_paths())[:6]:
                        node.open_local(path)
            rng = random.Random(99)
            for rnd in range(3):
                c.tick_step(rnd)
                for node in range(c.num_nodes):
                    if node in c.failed:
                        continue
                    batch = rng.sample(paths, 20)
                    if variant == "tier" and rnd == 1:
                        c.prefetch_window(node, rng.sample(paths, 12))
                    kw = dict(read_kw)
                    if variant == "serve":
                        kw["tenant"] = f"t{node % 2}"
                    got = c.read_many(node, batch, **kw)
                    want = [b"" if variant == "nomat" else expect[p]
                            for p in batch]
                    assert [bytes(d) for d in got] == want
                    for d in got:
                        digest.update(len(d).to_bytes(8, "little"))
                        digest.update(d)
            snap = {
                "payloads": digest.hexdigest(),
                "clocks": {n: dataclasses.asdict(k)
                           for n, k in c.clocks.items()},
                "wall": {n: _wall_counts(w)
                         for n, w in c.accounting.wall.items()},
                "stats": {n: dict(s.stats) for n, s in c.nodes.items()},
                "spans": _counters(SPANS.spans()),
                "faults": c.fault_stats(),
            }
    finally:
        SPANS.forced = was
        SPANS.clear()
    return json.loads(json.dumps(snap))


@pytest.fixture(scope="module")
def pinned():
    return json.loads(PINNED.read_text())


@pytest.mark.parametrize("case", CASES)
def test_read_many_matches_the_pinned_read_path(case, pinned):
    got = run_case(case)
    want = pinned[case]
    for key in ("payloads", "clocks", "wall", "stats", "spans", "faults"):
        assert got[key] == want[key], key
    if case.endswith("-kill"):
        faults = got["faults"]
        assert faults["killed"] and faults["injected"] > 0
        assert faults["retries"] == faults["injected"]


GATHER_SETTINGS = ["raw", "lzss", "tier", "unbatched", "nomat"]


@pytest.mark.parametrize("setting", GATHER_SETTINGS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_files_gathered_ties_out(backend, setting):
    """``files_gathered`` counts the files a batch's one-pass gather
    served: every local file and, on a wire that serves by the store's
    gather, every remote one, for a raw dataset with the tier off; none
    where the gather does not engage."""
    files = make_files()
    blobs, _ = prepare_dataset(files, 8, compress=setting == "lzss")
    spec = ClusterSpec(num_nodes=4, backend=backend,
                       cache_bytes=1 << 20 if setting == "tier" else 0)
    kw = {"unbatched": {"batched": False},
          "nomat": {"materialize": False}}.get(setting, {})
    paths = sorted(files)
    was = SPANS.forced
    SPANS.forced = True
    SPANS.clear()
    try:
        with FanStoreCluster.from_spec(spec) as c:
            c.load_partitions(blobs)
            for node in range(c.num_nodes):
                c.read_many(node, paths, **kw)
            gathers = c.transport.gathers
        reads = SPANS.spans("fanstore.read_many")
    finally:
        SPANS.forced = was
        SPANS.clear()
    assert len(reads) == 4
    for read in reads:
        k = read.counters
        assert k["files_local"] > 0 and k["files_remote"] > 0
        if setting == "raw":
            assert k["files_gathered"] == k["files_local"] + (
                k["files_remote"] if gathers else 0)
        else:
            assert k["files_gathered"] == 0
    if setting == "raw" and backend == "modeled":
        assert all(r.counters["files_gathered"] == len(paths) for r in reads)


def test_read_records_follow_the_partitions():
    """A store's read records come and go with its partitions: a copy
    made by ``replicate_partition`` serves the gather once the primary is
    failed, and ``drop_partition`` takes the records with the blob."""
    files = make_files(16)
    blobs, _ = prepare_dataset(files, 2, compress=False)
    with FanStoreCluster.from_spec(ClusterSpec(num_nodes=3)) as c:
        c.load_partitions(blobs)
        paths = [p for p in sorted(files)
                 if c.metadata.lookup(p)[1].node_id == 0]
        assert paths and all(c.nodes[2].read_record(p) is None
                             for p in paths)
        pid = c.metadata.lookup(paths[0])[1].partition_id
        c.replicate_partition(pid, src=0, dst=2)
        rec = c.nodes[2].read_record(paths[0])
        assert rec.item == c.nodes[0].read_record(paths[0]).item
        assert rec.blob[rec.start:rec.stop] == files[paths[0]]
        c.mark_failed(0)
        got = c.read_many(1, paths)
        assert [bytes(d) for d in got] == [files[p] for p in paths]
        c.nodes[2].drop_partition(pid)
        assert all(c.nodes[2].read_record(p) is None for p in paths)
        assert c.nodes[2].gather([]) == []


def pin():
    """Writes every case's values, one case a line."""
    PINNED.parent.mkdir(exist_ok=True)
    lines = [f"{json.dumps(c)}: {json.dumps(run_case(c), sort_keys=True)}"
             for c in CASES]
    PINNED.write_text("{\n" + ",\n".join(lines) + "\n}\n")


if __name__ == "__main__":
    if sys.argv[1:] != ["--pin"]:
        sys.exit("usage: python tests/test_read_gather.py --pin")
    pin()
