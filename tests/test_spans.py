"""Program spans: the recorder's nesting, ring and on/off contract, the
read path's counters against the ledgers, the loader's batch ids and its
put-wait and starvation spans, and the fold into a collector."""
import dataclasses
import queue
import threading

import numpy as np
import pytest

from repro.data.pipeline import PrefetchLoader
from repro.data.sampler import GlobalUniformSampler
from repro.fanstore.cluster import FanStoreCluster
from repro.fanstore.metrics import (SPANS, MetricsCollector, SpanRecorder,
                                    fold_spans)
from repro.fanstore.prepare import prepare_dataset
from repro.fanstore.spec import ClusterSpec

BACKENDS = ["modeled", "socket", "shm"]


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
    return {f"train/c{i % 4}/f_{i:03d}.bin":
            bytes((i * j * 2654435761) % 256 for j in range(300 + 7 * i))
            for i in range(n)}


@pytest.fixture(scope="module")
def dataset():
    files = make_files()
    blobs, _ = prepare_dataset(files, 8, compress=False)
    return files, blobs


def build(backend, blobs, **spec_kw):
    spec = ClusterSpec(num_nodes=4, backend=backend, **spec_kw)
    c = FanStoreCluster.from_spec(spec)
    c.load_partitions(blobs)
    return c


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_nesting_parent_thread_and_self_time():
    rec = SpanRecorder()
    rec.forced = True
    rec.set_batch(7)
    with rec.span("fanstore.outer") as outer:
        with rec.span("fanstore.inner") as inner:
            inner.add("files", 3)
            inner.add("files")
    rec.set_batch(None)
    with rec.span("fanstore.after") as after:
        pass
    got = rec.spans()
    assert [s.name for s in got] == ["fanstore.inner", "fanstore.outer",
                                     "fanstore.after"]
    assert inner.parent == outer.id and outer.parent is None
    assert after.parent is None and after.batch is None
    assert inner.batch == outer.batch == 7
    assert inner.thread == outer.thread == threading.get_ident()
    assert inner.counters == {"files": 4}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    self_ns = outer.duration_ns - inner.duration_ns
    assert 0 <= self_ns <= outer.duration_ns


def test_parent_is_per_thread():
    rec = SpanRecorder()
    rec.forced = True
    seen = {}

    def other():
        with rec.span("fanstore.other") as s:
            seen["span"] = s

    with rec.span("fanstore.main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["span"].parent is None
    assert seen["span"].thread != threading.get_ident()


def test_names_must_carry_the_program_prefix():
    rec = SpanRecorder()
    rec.forced = True
    with pytest.raises(ValueError, match="fanstore."):
        rec.span("bench.read_many")


def test_off_records_nothing_and_hands_out_one_falsy_span():
    rec = SpanRecorder()
    assert not rec.recording()          # no profiler session is active
    a, b = rec.span("fanstore.x"), rec.span("fanstore.y")
    assert a is b and not a
    with a as s:
        s.add("files", 3)
    rec.forced = True
    with rec.span("fanstore.z") as s:
        assert s
    rec.forced = False
    with rec.span("fanstore.w"):
        pass
    assert [s.name for s in rec.spans()] == ["fanstore.z"]


def test_ring_drops_the_oldest_and_counts_the_drop():
    rec = SpanRecorder(capacity=4)
    rec.forced = True
    dropped = []
    for i in range(6):
        with rec.span(f"fanstore.s{i}") as s:
            pass
        if i < 2:
            dropped.append(s)
    assert [s.name for s in rec.spans()] == [f"fanstore.s{i}"
                                             for i in range(2, 6)]
    assert rec.dropped == 2
    assert rec.dropped_start_ns == max(s.start_ns for s in dropped)
    assert len(rec.drain()) == 4 and rec.spans() == []
    rec.clear()
    assert rec.dropped == 0 and rec.dropped_start_ns is None


def test_default_ring_holds_at_least_65536_spans():
    assert SpanRecorder().capacity >= 65536


def test_follows_the_profiler_session(tmp_path):
    import jax
    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("fanstore.traced") as s:
            s.add("files", 2)
    finally:
        jax.profiler.stop_trace()
    with rec.span("fanstore.untraced"):
        pass
    assert [s.name for s in rec.spans()] == ["fanstore.traced"]


def test_fold_spans_into_a_collector():
    rec = SpanRecorder()
    rec.forced = True
    for n in (1, 2, 3):
        with rec.span("fanstore.fetch.remote") as s:
            s.counters.update(files=n, bytes=10 * n, account_ns=n)
    c = MetricsCollector()
    fold_spans(c, rec.drain())
    m = c.snapshot()["metrics"]
    assert m["fanstore.fetch.remote.files"]["value"] == 6
    assert m["fanstore.fetch.remote.bytes"]["value"] == 60
    assert m["fanstore.fetch.remote.account_ns"]["value"] == 6
    ms = m["fanstore.fetch.remote.ms"]
    assert ms["count"] == 3 and "p50" in ms and "p99" in ms


# ---------------------------------------------------------------------------
# the read path
# ---------------------------------------------------------------------------

def _reads(c, files):
    paths = sorted(files)
    out = []
    for node in range(c.num_nodes):
        out.append([bytes(d) for d in c.read_many(node, paths[node::2])])
        out.append([bytes(d) for d in c.read_many(node, paths[node::3])])
    return out


def _wall_counts(wall):
    """A WallClock without its nanosecond timings, which differ from run
    to run whatever records."""
    return {k: v for k, v in dataclasses.asdict(wall).items()
            if not k.endswith("_ns")}


@pytest.mark.parametrize("backend", BACKENDS)
def test_recording_changes_no_payload_and_no_clock(backend, dataset):
    files, blobs = dataset
    runs = {}
    was = SPANS.forced
    try:
        for on in (False, True):
            SPANS.forced = on
            SPANS.clear()
            with build(backend, blobs, cache_bytes=1 << 20) as c:
                payloads = _reads(c, files)
                runs[on] = (payloads, dict(c.clocks),
                            {n: _wall_counts(w)
                             for n, w in c.accounting.wall.items()})
            assert bool(SPANS.spans()) == on
    finally:
        SPANS.forced = was
        SPANS.clear()
    assert runs[True][0] == runs[False][0]
    assert runs[True][0][0] == [files[p] for p in sorted(files)[0::2]]
    assert runs[True][1] == runs[False][1]
    assert runs[True][2] == runs[False][2]


@pytest.mark.parametrize("backend", BACKENDS)
def test_read_counters_tie_out_with_the_ledgers(backend, dataset,
                                                recording):
    files, blobs = dataset
    paths = sorted(files)
    with build(backend, blobs, cache_bytes=1 << 20) as c:
        clock, wall = c.clocks[1], c.accounting.wall[1]
        before = (clock.local_bytes, clock.bytes_in, wall.requests)
        c.read_many(1, paths)
        (read,) = recording.spans("fanstore.read_many")
        (remote,) = recording.spans("fanstore.read_many.remote")
        legs = recording.spans("fanstore.fetch.remote")
        k = read.counters
        assert k["bytes_local"] == clock.local_bytes - before[0] > 0
        assert k["bytes_remote"] == clock.bytes_in - before[1] > 0
        assert k["files_local"] + k["files_remote"] == len(paths)
        assert k["files_local"] == sum(c.nodes[1].has(p) for p in paths)
        assert k["owners"] == len(legs) == 3
        assert k["cache_hits"] == 0 and k["retries"] == 0
        assert remote.parent == read.id
        assert all(leg.parent == remote.id for leg in legs)
        assert sum(leg.counters["files"] for leg in legs) == k["files_remote"]
        assert sum(leg.counters["bytes"] for leg in legs) == k["bytes_remote"]
        assert all(leg.counters["account_ns"] >= 0 for leg in legs)
        assert 0 <= k["local_ns"] <= read.duration_ns - remote.duration_ns
        if c.transport.measured:
            # one measured request per round trip and per local read
            assert wall.requests - before[2] == k["owners"] + k["files_local"]

        recording.clear()
        c.read_many(1, paths)            # every file now in the node tier
        (read,) = recording.spans("fanstore.read_many")
        assert read.counters["cache_hits"] == len(paths)
        assert read.counters["owners"] == 0
        assert recording.spans("fanstore.fetch.remote") == []


def test_retries_tie_out_with_the_retry_ledger(recording):
    files = make_files(48)
    blobs, _ = prepare_dataset(files, 16, compress=False)
    spec = ClusterSpec(num_nodes=4, replication=2, fault_threshold=10,
                       faults={"drop_fraction": 0.3, "seed": 3})
    c = FanStoreCluster.from_spec(spec)
    try:
        c.load_partitions(blobs, by_placement=True)
        paths = sorted(files)
        for node in range(4):
            got = c.read_many(node, paths)
            assert [bytes(d) for d in got] == [files[p] for p in paths]
        reads = recording.spans("fanstore.read_many")
        retries = sum(r.counters["retries"] for r in reads)
        assert retries == sum(cl.retries for cl in c.clocks.values()) > 0
        legs = [s for s in recording.spans("fanstore.fetch.remote")
                if "files" in s.counters]           # the ones that landed
        assert sum(r.counters["owners"] for r in reads) == len(legs)
    finally:
        c.close()


# ---------------------------------------------------------------------------
# the loader
# ---------------------------------------------------------------------------

def test_a_batch_shares_its_id_across_read_decode_and_get(dataset,
                                                          recording):
    files, blobs = dataset
    paths = sorted(files)
    with build("modeled", blobs) as c:
        sampler = GlobalUniformSampler(len(paths), 8, seed=1)
        loader = PrefetchLoader(
            sampler,
            fetch_many=lambda idxs: c.read_many(0, [paths[i] for i in idxs]),
            decode=lambda blobs_: [bytes(b) for b in blobs_], depth=2)
        ref = GlobalUniformSampler(len(paths), 8, seed=1)
        got = list(loader.batches(4))
        for batch in got:
            assert batch == [files[paths[i]] for i in ref.next_batch()]
    by_name = {}
    for s in recording.spans():
        by_name.setdefault(s.name, []).append(s)
    gets = [s for s in by_name["fanstore.loader.get"] if s.batch is not None]
    assert len(gets) == 4
    ids = [s.batch for s in gets]
    assert len(set(ids)) == 4
    for name in ("fanstore.loader.fetch", "fanstore.read_many",
                 "fanstore.read_many.remote", "fanstore.loader.decode",
                 "fanstore.loader.put_wait"):
        assert sorted(s.batch for s in by_name[name]) == sorted(ids), name
    fetch = {s.batch: s for s in by_name["fanstore.loader.fetch"]}
    for read in by_name["fanstore.read_many"]:
        assert read.parent == fetch[read.batch].id


class _SignallingQueue(queue.Queue):
    """Sets ``blocked`` when a put finds the queue full and ``getting``
    when a blocking get begins, so a test can order threads without
    sleeping."""

    def __init__(self, maxsize):
        super().__init__(maxsize)
        self.blocked = threading.Event()
        self.getting = threading.Event()

    def put(self, item, block=True, timeout=None):
        if self.full():
            self.blocked.set()
        super().put(item, block, timeout)

    def get(self, block=True, timeout=None):
        if block:                        # not the loader's own drain
            self.getting.set()
        return super().get(block, timeout)


def _loader(fetch_many, depth):
    loader = PrefetchLoader(GlobalUniformSampler(32, 4, seed=0),
                            fetch_many=fetch_many,
                            decode=lambda b: list(b), depth=depth)
    loader._q = _SignallingQueue(depth)
    return loader


def test_put_wait_is_recorded_when_the_consumer_is_slow(recording):
    loader = _loader(lambda idxs: [bytes([i]) for i in idxs], depth=1)
    loader.start(3)
    try:
        assert loader._q.blocked.wait(timeout=30)   # batch 2 finds it full
        first = next(loader)
        assert first is not None
        rest = list(loader)
    finally:
        loader.close()
    assert len(rest) == 2
    waits = sorted(recording.spans("fanstore.loader.put_wait"),
                   key=lambda s: s.batch)
    (get0,) = [s for s in recording.spans("fanstore.loader.get")
               if s.batch == waits[0].batch]
    assert len(waits) == 3
    assert waits[0].counters["full"] == 0
    assert waits[1].counters["full"] == 1
    # the blocked put could only finish once the consumer took batch 1
    assert waits[1].end_ns >= get0.start_ns


def test_starved_is_counted_when_the_producer_is_slow(recording):
    release = threading.Event()

    def fetch_many(idxs):
        assert release.wait(timeout=30)
        return [bytes([i]) for i in idxs]

    loader = _loader(fetch_many, depth=2)

    def releaser():
        loader._q.getting.wait(timeout=30)  # the consumer is inside get
        release.set()

    t = threading.Thread(target=releaser)
    t.start()
    loader.start(2)
    try:
        batches = list(loader)
    finally:
        release.set()
        t.join(timeout=30)
        loader.close()
    assert not t.is_alive()
    assert len(batches) == 2
    gets = [s for s in recording.spans("fanstore.loader.get")
            if s.batch is not None]
    assert gets[0].counters["starved"] == 1
    fetch0 = min(recording.spans("fanstore.loader.fetch"),
                 key=lambda s: s.start_ns)
    assert gets[0].end_ns >= fetch0.end_ns
    assert recording.spans("fanstore.loader.put_wait")[0].batch == \
        gets[0].batch


def test_per_sample_fetch_threads_carry_the_batch_id(recording):
    seen = []

    def fetch(i):
        seen.append(SPANS.batch())
        return bytes([i])

    loader = PrefetchLoader(GlobalUniformSampler(16, 4, seed=0), fetch,
                            lambda b: np.frombuffer(b"".join(b), np.uint8),
                            num_threads=3)
    out = list(loader.batches(2))
    assert len(out) == 2
    ids = sorted({s.batch for s in recording.spans("fanstore.loader.fetch")})
    assert len(ids) == 2 and sorted(set(seen)) == ids


def test_train_metrics_jsonl_carries_the_span_folds(tmp_path):
    from repro.fanstore.metrics import JsonlSink
    from repro.launch import train
    path = tmp_path / "m.jsonl"
    args = train.parse_args([
        "--arch", "hymba-1.5b", "--preset", "smoke", "--layers", "2",
        "--seq-len", "16", "--global-batch", "4", "--num-samples", "32",
        "--steps", "3", "--seed", "3", "--metrics-jsonl", str(path)])
    was = SPANS.forced
    train.run(args)
    assert SPANS.forced is was                 # the option's forcing ends
    m = JsonlSink.load(path)[-1]["metrics"]
    read = m["fanstore.read_many.ms"]
    assert read["count"] >= 3 and read["p50"] > 0 and read["p99"] > 0
    assert m["fanstore.read_many.files_local"]["value"] + \
        m["fanstore.read_many.files_remote"]["value"] == 4 * read["count"]
    # a raw dataset, no cache tier, the modeled wire: the gather serves all
    assert m["fanstore.read_many.files_gathered"]["value"] == \
        4 * read["count"]
    for name in ("fanstore.loader.fetch", "fanstore.loader.decode",
                 "fanstore.loader.put_wait", "fanstore.loader.get"):
        assert m[f"{name}.ms"]["count"] >= 3, name
