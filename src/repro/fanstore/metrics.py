"""Observability plane: metric accumulators, reduce modes, and a
streaming telemetry pipeline.

The engine's clocks (:mod:`repro.fanstore.accounting`) are rich but
passive — every benchmark and driver used to hand-roll its own dict
plumbing to get numbers out. This module is the one pipeline they all
emit through now:

* :class:`Reduce` / :class:`Mode` — how a series folds (SUM / MEAN /
  MAX / MIN / COUNT / P50 / P99) and whether the collector keeps
  (node, worker)-keyed series (``PER_RANK``) or folds them across the
  topology at flush (``GLOBAL_REDUCE``).
* :class:`QuantileSketch` — bounded-memory streaming quantiles behind
  P50/P99: a capacity-``C`` buffer of (value, weight) clusters that
  pairwise-merges adjacent clusters when full, so memory stays O(C)
  independent of sample count and the rank error stays ~2/C.
* The :class:`MetricAccumulator` hierarchy — :class:`ScalarAccumulator`
  (sum/count/min/max), :class:`DistributionAccumulator` (scalar stats +
  sketch), :class:`RateAccumulator` (value per wall-clock second).
* :class:`MetricsCollector` — thread-safe, owned by the cluster
  (``cluster.metrics``). ``record_metric(name, value, reduce=...)``
  takes only the collector's OWN lock, never the clock lock, so
  serving-loop / stripe / prefetch threads can flush into it without
  contending accrual. The ledger bridge happens at ``snapshot()`` time
  via :meth:`repro.fanstore.accounting.ClusterAccounting.snapshot` —
  one consistent copy of lane seconds, cache hit rates, tenant/job
  attribution, retry/fault counters, stripe bytes, and wire codec
  savings.
* :class:`JsonlSink` — streaming, crash-safe append of monotonically
  versioned snapshots: one JSON object per line, periodic
  (:meth:`JsonlSink.tick`) + explicit (:meth:`JsonlSink.flush`)
  flushes, size-based rotation, and a reloader that tolerates a torn
  trailing line (the crash case append-only files actually hit).
* :class:`SloGuard` / :func:`check_slos` — declarative threshold checks
  over a snapshot document (dotted paths with ``*`` wildcards,
  cross-path :class:`Ref` comparisons, conditional ``when`` clauses).
  ``benchmarks/run.py`` expresses every BENCH_io.json guard as a table
  of these instead of assert soup.
* :class:`SpanRecorder` / :data:`SPANS` — the program's own spans
  (``fanstore.*``) inside the read path and the loader, on
  ``time.perf_counter_ns``, each with its parent, thread, batch id and
  integer counters, kept in a bounded ring. It records only while a JAX
  profiler session is active (each span then also lands in the trace as
  a ``jax.profiler.TraceAnnotation``) or while forced on;
  :func:`fold_spans` folds recorded spans into a collector.

Provenance discipline: everything under ``snapshot()["nodes"][i]
["modeled"]`` / ``["cluster"]`` modeled aggregates is deterministic
model output; everything under ``["measured"]`` is hardware truth from
the real-wire backends. App-level series recorded through
``record_metric`` are whatever the caller measured (see the metric
catalog in ARCHITECTURE.md).
"""
from __future__ import annotations

import collections
import copy
import enum
import itertools
import json
import os
import sys
import threading
import time
import weakref
from dataclasses import dataclass
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple, Union)

__all__ = [
    "Reduce", "Mode", "QuantileSketch",
    "MetricAccumulator", "ScalarAccumulator", "DistributionAccumulator",
    "RateAccumulator", "make_accumulator",
    "MetricsCollector", "JsonlSink",
    "SloGuard", "Ref", "check_slos", "resolve_path",
    "Span", "SpanRecorder", "SPANS", "SPAN_PREFIX", "fold_spans",
]


class Reduce(enum.Enum):
    """How a metric series folds to one number."""
    SUM = "sum"
    MEAN = "mean"
    MAX = "max"
    MIN = "min"
    COUNT = "count"
    P50 = "p50"
    P99 = "p99"


class Mode(enum.Enum):
    """Collection mode: keep (node, worker)-keyed series, or fold them
    across the topology at flush. The collector always STORES per-rank
    (so the two modes are views of the same data and provably agree
    under reduction); the mode picks what ``snapshot()`` renders."""
    PER_RANK = "per_rank"
    GLOBAL_REDUCE = "global_reduce"


# ---------------------------------------------------------------------------
# bounded-memory quantile sketch
# ---------------------------------------------------------------------------
class QuantileSketch:
    """Streaming quantile estimator with O(capacity) memory.

    Keeps at most ``capacity`` (value, weight) clusters, each value a
    REAL observed sample. When the buffer fills, adjacent clusters
    (after a sort by value) pairwise-merge — the heavier member's value
    survives with the pair's combined weight — halving the buffer in one
    pass. Each compaction at most doubles the maximum cluster weight,
    and ``n`` samples fit in ``log2(2n/capacity)`` compactions, so the
    worst-case cluster weight — and therefore the absolute rank error of
    :meth:`query` — is about ``2n/capacity`` (relative rank error
    ``~2/capacity``). ``capacity=512`` gives <1% rank error, enough to
    tell a 10x P99 regression from noise at any sample count.
    """

    __slots__ = ("capacity", "_entries", "compactions")

    def __init__(self, capacity: int = 512):
        if capacity < 8:
            raise ValueError("sketch capacity must be >= 8")
        self.capacity = int(capacity)
        self._entries: List[Tuple[float, int]] = []  # (value, weight)
        self.compactions = 0

    def __len__(self) -> int:
        """Number of retained clusters — bounded by ``capacity``."""
        return len(self._entries)

    @property
    def count(self) -> int:
        """Total weight observed (== number of ``add(w=1)`` calls)."""
        return sum(w for _, w in self._entries)

    def add(self, value: float, weight: int = 1) -> None:
        self._entries.append((float(value), int(weight)))
        if len(self._entries) > self.capacity:
            self._compact()

    def merge(self, other: "QuantileSketch") -> None:
        """Fold another sketch in (GLOBAL_REDUCE across ranks)."""
        self._entries.extend(other._entries)
        while len(self._entries) > self.capacity:
            self._compact()

    def _compact(self) -> None:
        """Sort by value and merge adjacent pairs, keeping the heavier
        member's (real) value with the pair's combined weight."""
        self._entries.sort()
        merged: List[Tuple[float, int]] = []
        it = iter(self._entries)
        for a in it:
            b = next(it, None)
            if b is None:
                merged.append(a)
            else:
                keep = a[0] if a[1] >= b[1] else b[0]
                merged.append((keep, a[1] + b[1]))
        self._entries = merged
        self.compactions += 1

    def query(self, q: float) -> float:
        """Value at quantile ``q`` in [0, 1] (0.0 when empty)."""
        if not self._entries:
            return 0.0
        q = min(1.0, max(0.0, float(q)))
        entries = sorted(self._entries)
        total = sum(w for _, w in entries)
        target = q * total
        cum = 0
        for value, weight in entries:
            cum += weight
            if cum >= target:
                return value
        return entries[-1][0]


# ---------------------------------------------------------------------------
# accumulator hierarchy
# ---------------------------------------------------------------------------
class MetricAccumulator:
    """One metric series' state for one rank. Subclasses define what is
    retained; :meth:`value` folds it per the declared :class:`Reduce`.
    NOT thread-safe on its own — the collector serializes access."""

    kind = "abstract"

    def __init__(self, reduce: Reduce):
        self.reduce = reduce

    def observe(self, value: float) -> None:
        raise NotImplementedError

    def merge(self, other: "MetricAccumulator") -> None:
        raise NotImplementedError

    def value(self) -> float:
        raise NotImplementedError

    def summary(self) -> Dict[str, Any]:
        raise NotImplementedError

    def clone(self) -> "MetricAccumulator":
        return copy.deepcopy(self)


class ScalarAccumulator(MetricAccumulator):
    """sum / count / min / max — answers SUM, MEAN, MAX, MIN, COUNT."""

    kind = "scalar"

    def __init__(self, reduce: Reduce = Reduce.SUM):
        if reduce in (Reduce.P50, Reduce.P99):
            raise ValueError(
                f"{reduce.name} needs a DistributionAccumulator")
        super().__init__(reduce)
        self.sum = 0.0
        self.count = 0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def observe(self, value: float) -> None:
        v = float(value)
        self.sum += v
        self.count += 1
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def merge(self, other: "MetricAccumulator") -> None:
        self.sum += other.sum
        self.count += other.count
        for attr, pick in (("min", min), ("max", max)):
            mine, theirs = getattr(self, attr), getattr(other, attr)
            if theirs is not None:
                setattr(self, attr,
                        theirs if mine is None else pick(mine, theirs))

    def value(self) -> float:
        r = self.reduce
        if r is Reduce.SUM:
            return self.sum
        if r is Reduce.COUNT:
            return float(self.count)
        if r is Reduce.MEAN:
            return self.sum / self.count if self.count else 0.0
        if r is Reduce.MAX:
            return self.max if self.max is not None else 0.0
        if r is Reduce.MIN:
            return self.min if self.min is not None else 0.0
        raise ValueError(f"unhandled reduce {r}")  # pragma: no cover

    def summary(self) -> Dict[str, Any]:
        return {"count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max}


class DistributionAccumulator(ScalarAccumulator):
    """Scalar stats plus a bounded-memory sketch — adds P50 / P99."""

    kind = "distribution"

    def __init__(self, reduce: Reduce = Reduce.P99,
                 sketch_capacity: int = 512):
        MetricAccumulator.__init__(self, reduce)
        self.sum = 0.0
        self.count = 0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.sketch = QuantileSketch(sketch_capacity)

    def observe(self, value: float) -> None:
        ScalarAccumulator.observe(self, value)
        self.sketch.add(float(value))

    def merge(self, other: "MetricAccumulator") -> None:
        ScalarAccumulator.merge(self, other)
        if isinstance(other, DistributionAccumulator):
            self.sketch.merge(other.sketch)

    def value(self) -> float:
        if self.reduce is Reduce.P50:
            return self.sketch.query(0.50)
        if self.reduce is Reduce.P99:
            return self.sketch.query(0.99)
        return ScalarAccumulator.value(self)

    def summary(self) -> Dict[str, Any]:
        out = ScalarAccumulator.summary(self)
        out["p50"] = self.sketch.query(0.50)
        out["p99"] = self.sketch.query(0.99)
        return out


class RateAccumulator(ScalarAccumulator):
    """Accumulated value per wall-clock second since the series was
    born (e.g. bytes/s). The reduce must be SUM — the rate is the sum
    divided by elapsed time; folding across ranks takes the earliest
    birth (the window every rank's traffic shares)."""

    kind = "rate"

    def __init__(self, reduce: Reduce = Reduce.SUM,
                 clock: Callable[[], float] = time.monotonic):
        if reduce is not Reduce.SUM:
            raise ValueError("rate metrics reduce as SUM over elapsed time")
        super().__init__(reduce)
        self._clock = clock
        self.start = clock()

    def merge(self, other: "MetricAccumulator") -> None:
        ScalarAccumulator.merge(self, other)
        if isinstance(other, RateAccumulator):
            self.start = min(self.start, other.start)

    @property
    def elapsed_s(self) -> float:
        return max(self._clock() - self.start, 1e-9)

    def value(self) -> float:
        return self.sum / self.elapsed_s

    def summary(self) -> Dict[str, Any]:
        out = ScalarAccumulator.summary(self)
        out["elapsed_s"] = self.elapsed_s
        return out


def make_accumulator(reduce: Reduce, *, rate: bool = False,
                     sketch_capacity: int = 512,
                     clock: Callable[[], float] = time.monotonic,
                     ) -> MetricAccumulator:
    """Route a (reduce, rate) declaration to its accumulator class."""
    if rate:
        return RateAccumulator(reduce, clock=clock)
    if reduce in (Reduce.P50, Reduce.P99):
        return DistributionAccumulator(reduce, sketch_capacity)
    return ScalarAccumulator(reduce)


# ---------------------------------------------------------------------------
# collector
# ---------------------------------------------------------------------------
RankKey = Optional[Tuple[int, int]]


def _rank_str(rank: RankKey) -> str:
    return "global" if rank is None else f"{rank[0]}/{rank[1]}"


class MetricsCollector:
    """Thread-safe metric registry, one per cluster (``cluster.metrics``).

    Recording takes ONLY the collector's own lock — never the clock
    lock — so serving-loop / stripe / prefetch threads flush app-level
    series in without contending accrual. Series are always stored
    per-rank (``rank=(node, worker)``, or the ``global`` rank when
    unranked); :class:`Mode` picks whether ``snapshot()`` renders the
    keyed series (PER_RANK) or only the topology fold (GLOBAL_REDUCE),
    so the two modes agree under reduction by construction.

    ``snapshot()`` additionally bridges every accounting ledger through
    one consistent :meth:`~repro.fanstore.accounting.ClusterAccounting.
    snapshot` copy, plus the cluster's fault counters when a cluster is
    attached. Snapshots are monotonically versioned (the version
    survives :meth:`reset`, so a JSONL stream never repeats one).
    """

    def __init__(self, accounting=None, *, cluster=None,
                 mode: Mode = Mode.GLOBAL_REDUCE,
                 sketch_capacity: int = 512,
                 clock: Callable[[], float] = time.monotonic):
        self.accounting = accounting if accounting is not None else (
            cluster.accounting if cluster is not None else None)
        # weakref: the cluster owns its collector (cluster.metrics), so a
        # strong back-reference would make a cycle and keep an abandoned
        # cluster — and its lazily spawned transport pool threads — alive
        # until the cycle GC runs instead of dying by refcount
        self._cluster = (weakref.ref(cluster)
                         if cluster is not None else None)
        self.mode = Mode(mode)
        self.sketch_capacity = int(sketch_capacity)
        self.clock = clock
        self._lock = threading.Lock()
        self._series: Dict[str, Dict[RankKey, MetricAccumulator]] = {}
        self._decl: Dict[str, Tuple[Reduce, bool]] = {}
        self._blocks: Dict[str, Any] = {}
        self._version = 0

    @property
    def cluster(self):
        """The owning cluster, or None once it has been collected."""
        return self._cluster() if self._cluster is not None else None

    # ---- recording ---------------------------------------------------------
    def record_metric(self, name: str, value: float, *,
                      reduce: Reduce = Reduce.SUM,
                      rank: RankKey = None,
                      rate: bool = False) -> None:
        """Observe one value on the series ``name`` (for ``rank``).

        A name binds to ONE (reduce, rate) declaration for the life of
        the collector; a conflicting re-declaration raises rather than
        silently forking the series.
        """
        reduce = Reduce(reduce)
        if rank is not None:
            rank = (int(rank[0]), int(rank[1]))
        with self._lock:
            decl = self._decl.get(name)
            if decl is None:
                self._decl[name] = (reduce, rate)
            elif decl != (reduce, rate):
                raise ValueError(
                    f"metric {name!r} already declared as "
                    f"(reduce={decl[0].name}, rate={decl[1]}); got "
                    f"(reduce={reduce.name}, rate={rate})")
            ranks = self._series.setdefault(name, {})
            acc = ranks.get(rank)
            if acc is None:
                acc = make_accumulator(
                    reduce, rate=rate,
                    sketch_capacity=self.sketch_capacity, clock=self.clock)
                ranks[rank] = acc
            acc.observe(value)

    def record_block(self, name: str, block: Any) -> None:
        """Attach one structured, JSON-ready benchmark block. Snapshots
        re-emit the blocks verbatim under ``"bench"`` — this is how
        ``benchmarks/run.py`` routes BENCH_io.json through the pipeline
        without changing the emitted schema."""
        with self._lock:
            self._blocks[name] = copy.deepcopy(block)

    def reset(self) -> None:
        """Drop every series and block. The snapshot version is NOT
        reset — it stays monotonic across the collector's life."""
        with self._lock:
            self._series.clear()
            self._decl.clear()
            self._blocks.clear()

    # ---- views -------------------------------------------------------------
    @staticmethod
    def _fold(ranks: Dict[RankKey, MetricAccumulator]) -> MetricAccumulator:
        accs = list(ranks.values())
        folded = accs[0].clone()
        for a in accs[1:]:
            folded.merge(a)
        return folded

    @staticmethod
    def _entry(acc: MetricAccumulator) -> Dict[str, Any]:
        out = {"reduce": acc.reduce.value, "kind": acc.kind,
               "value": acc.value()}
        out.update(acc.summary())
        return out

    def snapshot(self, *, mode: Optional[Mode] = None) -> Dict[str, Any]:
        """One monotonically versioned, JSON-ready view of everything:
        recorded series (folded, plus per-rank under PER_RANK), attached
        bench blocks, and the full accounting-ledger bridge."""
        mode = self.mode if mode is None else Mode(mode)
        # ledgers first (clock lock), then our lock — never nested
        ledgers = (self.accounting.snapshot()
                   if self.accounting is not None else None)
        out: Dict[str, Any] = {"schema": 1, "mode": mode.value}
        with self._lock:
            self._version += 1
            out["version"] = self._version
            metrics: Dict[str, Any] = {}
            for name in sorted(self._series):
                ranks = self._series[name]
                entry = self._entry(self._fold(ranks))
                if mode is Mode.PER_RANK:
                    entry["ranks"] = {
                        _rank_str(r): self._entry(a)
                        for r, a in sorted(
                            ranks.items(),
                            key=lambda kv: _rank_str(kv[0]))}
                metrics[name] = entry
            out["metrics"] = metrics
            if self._blocks:
                out["bench"] = copy.deepcopy(self._blocks)
        if ledgers is not None:
            out["nodes"] = ledgers["nodes"]
            out["cluster"] = ledgers["cluster"]
        cluster = self.cluster     # deref the weakref once
        if cluster is not None:
            out["faults"] = cluster.fault_stats()
        return out

    def rank_view(self, node: int, worker: int) -> Dict[str, Any]:
        """The PER_RANK slice one bound session sees: its own recorded
        series plus its node's lanes and its worker-attributed cache
        counters (``FanStoreSession.metrics()``)."""
        rank = (int(node), int(worker))
        out: Dict[str, Any] = {"rank": _rank_str(rank), "metrics": {}}
        with self._lock:
            for name, ranks in sorted(self._series.items()):
                if rank in ranks:
                    out["metrics"][name] = self._entry(ranks[rank])
        if self.accounting is not None:
            nodes = self.accounting.snapshot()["nodes"]
            nd = nodes.get(rank[0])
            if nd is not None:
                m = nd["modeled"]
                out["node"] = {k: m[k] for k in (
                    "consume_s", "serve_s", "prefetch_s", "write_s",
                    "serve_app_s", "busy_s", "bytes_in", "local_bytes",
                    "cache_hit_rate")}
                out["cache"] = {
                    "hits": m["worker_cache_hits"].get(rank[1], 0),
                    "misses": m["worker_cache_misses"].get(rank[1], 0),
                    "hit_bytes":
                        m["worker_cache_hit_bytes"].get(rank[1], 0)}
        return out

    def flush(self, sink: Optional["JsonlSink"] = None, *,
              mode: Optional[Mode] = None) -> Dict[str, Any]:
        """Take a snapshot and (when a sink is given) append it."""
        snap = self.snapshot(mode=mode)
        if sink is not None:
            sink.emit(snap)
        return snap


# ---------------------------------------------------------------------------
# program spans
# ---------------------------------------------------------------------------
SPAN_PREFIX = "fanstore."
SPAN_CAPACITY = 1 << 18     # about 100 MB at most; a batch opens about 9


class _SpanLocal(threading.local):
    span: Optional["Span"] = None      # innermost open span on this thread
    batch: Optional[int] = None        # batch the thread is working on


class Span:
    """One timed region of the program.

    ``start_ns``/``end_ns`` are ``time.perf_counter_ns`` readings;
    ``parent`` is the id of the span open around this one on the same
    thread (None at the top), ``batch`` the batch id the thread was
    working on when the span opened, ``counters`` integer counts. While
    open it is also a ``jax.profiler.TraceAnnotation`` of the same name
    (once JAX's profiler is imported), so it lands in a profiler trace on
    the device trace's clock, with its counters as metadata.
    """

    __slots__ = ("name", "id", "parent", "thread", "batch", "start_ns",
                 "end_ns", "counters", "_rec", "_up", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str):
        self.name = name
        self.id = next(rec._ids)
        self.counters: Dict[str, int] = {}
        self._rec = rec
        self.start_ns = self.end_ns = 0

    def add(self, key: str, n: int = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def __enter__(self) -> "Span":
        local = self._rec._local
        up = local.span
        self._up = up
        self.parent = up.id if up is not None else None
        self.thread = threading.get_ident()
        self.batch = local.batch
        local.span = self
        annotation = self._rec._annotation()
        self._ann = annotation(self.name) if annotation is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self._ann is not None:
            if self.counters:
                self._ann.set_metadata(**self.counters)
            self._ann.__exit__(*exc)
            self._ann = None
        self._rec._local.span = self._up
        self._up = None
        self._rec._push(self)
        return False


class _NullSpan:
    """What a span site gets while nothing records: falsy, and shared."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def add(self, key: str, n: int = 1) -> None:
        pass


_NULL_SPAN = _NullSpan()


class SpanRecorder:
    """The process-wide recorder of program spans (:data:`SPANS`).

    It records while a JAX profiler session is active
    (``TraceAnnotation.is_enabled()``), unless ``forced`` says otherwise:
    True records always, False never, None (the default) follows the
    profiler. While it does not record, :meth:`span` costs one flag
    check and returns a shared, falsy no-op span, so a site can guard
    extra timing with ``if span:``. Recorded spans go into a ring of
    ``capacity`` spans; when it is full the oldest is dropped,
    ``dropped`` counts it and ``dropped_start_ns`` keeps the latest start
    of a dropped span, so a reader can tell whether the spans it wants
    are whole. Every name starts with ``fanstore.``.
    """

    def __init__(self, capacity: int = SPAN_CAPACITY):
        if capacity < 1:
            raise ValueError("span capacity must be >= 1")
        self.capacity = int(capacity)
        self.forced: Optional[bool] = None
        self.dropped = 0
        self.dropped_start_ns: Optional[int] = None
        self._ring: "collections.deque[Span]" = collections.deque()
        self._lock = threading.Lock()
        self._local = _SpanLocal()
        self._ids = itertools.count(1)
        self._batch_ids = itertools.count(1)
        self._trace_annotation = None

    def _annotation(self):
        """``jax.profiler.TraceAnnotation``, once something has imported
        JAX's profiler (no profiler session can be active before)."""
        annotation = self._trace_annotation
        if annotation is None:
            mod = sys.modules.get("jax.profiler")
            if mod is not None:
                annotation = self._trace_annotation = mod.TraceAnnotation
        return annotation

    def recording(self) -> bool:
        forced = self.forced
        if forced is not None:
            return forced
        annotation = self._annotation()
        return annotation is not None and annotation.is_enabled()

    def span(self, name: str):
        """A context manager timing one region, or the no-op span."""
        if not self.recording():
            return _NULL_SPAN
        if not name.startswith(SPAN_PREFIX):
            raise ValueError(f"span name {name!r} must start with "
                             f"{SPAN_PREFIX!r}")
        return Span(self, name)

    def next_batch(self) -> int:
        """A batch id unique in the process (across loaders)."""
        return next(self._batch_ids)

    def set_batch(self, batch: Optional[int]) -> None:
        """Tags the spans this thread opens from now on with ``batch``."""
        self._local.batch = batch

    def batch(self) -> Optional[int]:
        """The batch id this thread's spans are tagged with."""
        return self._local.batch

    def _push(self, span: Span) -> None:
        with self._lock:
            if len(self._ring) >= self.capacity:
                old = self._ring.popleft()
                self.dropped += 1
                if (self.dropped_start_ns is None
                        or old.start_ns > self.dropped_start_ns):
                    self.dropped_start_ns = old.start_ns
            self._ring.append(span)

    def spans(self, name: Optional[str] = None) -> List[Span]:
        """A copy of the ring (oldest first), optionally of one name."""
        with self._lock:
            out = list(self._ring)
        return out if name is None else [s for s in out if s.name == name]

    def drain(self) -> List[Span]:
        """Takes every recorded span out of the ring."""
        with self._lock:
            out = list(self._ring)
            self._ring.clear()
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self.dropped_start_ns = None


SPANS = SpanRecorder()


def fold_spans(collector: MetricsCollector, spans: Iterable[Span]) -> None:
    """Folds spans into ``collector``: per span name, its duration in ms
    as ``<name>.ms`` (P99; the summary carries P50 too) and the sum of
    each counter as ``<name>.<counter>``."""
    for s in spans:
        collector.record_metric(f"{s.name}.ms", s.duration_ns / 1e6,
                                reduce=Reduce.P99)
        for key, n in s.counters.items():
            collector.record_metric(f"{s.name}.{key}", n, reduce=Reduce.SUM)


# ---------------------------------------------------------------------------
# streaming sink
# ---------------------------------------------------------------------------
class JsonlSink:
    """Append-only JSONL stream of snapshots: one JSON object per line.

    Crash-safe by construction — each :meth:`emit` appends one complete
    line and flushes the OS buffer before returning, so a crash can tear
    at most the line being written, and :meth:`load` tolerates exactly
    that (a torn FINAL line is dropped; a torn middle line is real
    corruption and raises). Size-based rotation renames the live file to
    ``<path>.1``, ``<path>.2``, ... before the append that would
    overflow ``rotate_bytes``.
    """

    def __init__(self, path, *, every_s: Optional[float] = None,
                 rotate_bytes: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.path = str(path)
        self.every_s = every_s
        self.rotate_bytes = rotate_bytes
        self.clock = clock
        self._lock = threading.Lock()
        self._fh = None
        self._last_emit: Optional[float] = None
        self.rotations = 0
        self.records_written = 0

    # -- write side ----------------------------------------------------------
    def _open(self):
        if self._fh is None:
            self._fh = open(self.path, "a", encoding="utf-8")
        return self._fh

    def emit(self, record: Dict[str, Any]) -> None:
        """Append one record now (explicit flush)."""
        line = json.dumps(record, sort_keys=True, default=str) + "\n"
        data = line.encode("utf-8")
        with self._lock:
            fh = self._open()
            if (self.rotate_bytes is not None and fh.tell() > 0
                    and fh.tell() + len(data) > self.rotate_bytes):
                fh.close()
                self._fh = None
                self.rotations += 1
                os.replace(self.path, f"{self.path}.{self.rotations}")
                fh = self._open()
            fh.write(line)
            fh.flush()
            self.records_written += 1
            self._last_emit = self.clock()

    def tick(self, collector: MetricsCollector, *,
             mode: Optional[Mode] = None) -> bool:
        """Periodic flush: emit a snapshot when ``every_s`` has elapsed
        since the last emission (always emits when ``every_s`` is None
        or nothing was emitted yet). Returns whether it emitted."""
        with self._lock:
            due = (self.every_s is None or self._last_emit is None
                   or self.clock() - self._last_emit >= self.every_s)
        if due:
            self.emit(collector.snapshot(mode=mode))
        return due

    def flush(self, collector: MetricsCollector, *,
              mode: Optional[Mode] = None) -> Dict[str, Any]:
        """Explicit flush: emit a snapshot unconditionally."""
        snap = collector.snapshot(mode=mode)
        self.emit(snap)
        return snap

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self) -> "JsonlSink":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- read side -----------------------------------------------------------
    @staticmethod
    def load(path, *, include_rotated: bool = True) -> List[Dict[str, Any]]:
        """Reload a stream (rotated segments first, oldest to newest).
        A torn trailing line in the LIVE file is dropped; corruption
        anywhere else raises ``ValueError``."""
        path = str(path)
        files: List[str] = []
        if include_rotated:
            k = 1
            while os.path.exists(f"{path}.{k}"):
                files.append(f"{path}.{k}")
                k += 1
        if os.path.exists(path):
            files.append(path)
        records: List[Dict[str, Any]] = []
        for fname in files:
            with open(fname, "r", encoding="utf-8") as fh:
                lines = [ln for ln in fh.read().splitlines() if ln.strip()]
            for i, ln in enumerate(lines):
                try:
                    records.append(json.loads(ln))
                except json.JSONDecodeError:
                    if fname == path and i == len(lines) - 1:
                        break  # torn tail from a crash mid-append
                    raise ValueError(
                        f"corrupt JSONL record in {fname} line {i + 1}")
        return records


# ---------------------------------------------------------------------------
# declarative SLO guards
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Ref:
    """A threshold that is itself a path into the document. Wildcards in
    the ref path consume the metric path's wildcard bindings in order;
    any LEFTOVER ref wildcards expand to a for-all comparison (e.g.
    "belady >= every policy on the same arm")."""
    path: str


@dataclass(frozen=True)
class SloGuard:
    """One declarative threshold check over a snapshot document.

    ``metric`` is a dotted path (``*`` matches every dict value / list
    element); ``op`` one of ``> >= < <= == != truthy nonempty min_len
    subset in``; ``threshold`` a literal or a :class:`Ref`; ``when`` an
    optional ``(path, op, literal)`` gate — when it does not hold, the
    guard is skipped. A metric path that matches NOTHING is itself a
    violation (guards fail loudly on missing data).
    """
    name: str
    metric: str
    op: str
    threshold: Any = None
    when: Optional[Tuple[str, str, Any]] = None


def resolve_path(doc: Any, path: str) -> List[Tuple[Tuple, Any]]:
    """Resolve a dotted path with ``*`` wildcards against nested
    dicts/lists; returns ``[(bindings, value), ...]`` where bindings are
    the keys/indices each ``*`` matched, in order. Dict keys may
    themselves contain dots (metric names like ``train.loss``): at each
    dict the LONGEST joined run of remaining segments that names a key
    wins, so ``metrics.train.loss.value`` finds
    ``doc["metrics"]["train.loss"]["value"]``."""
    parts = path.split(".")
    out: List[Tuple[Tuple, Any]] = []

    def walk(node: Any, i: int, bindings: List) -> None:
        if i == len(parts):
            out.append((tuple(bindings), node))
            return
        p = parts[i]
        if p == "*":
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(v, i + 1, bindings + [k])
            elif isinstance(node, (list, tuple)):
                for j, v in enumerate(node):
                    walk(v, i + 1, bindings + [j])
        elif isinstance(node, dict):
            for j in range(len(parts), i, -1):
                key = ".".join(parts[i:j])
                if key in node:
                    walk(node[key], j, bindings)
                    return
        elif isinstance(node, (list, tuple)):
            try:
                idx = int(p)
            except ValueError:
                return
            if -len(node) <= idx < len(node):
                walk(node[idx], i + 1, bindings)

    walk(doc, 0, [])
    return out


def _substitute(ref_path: str, bindings: Tuple) -> str:
    parts = ref_path.split(".")
    bi = 0
    for i, p in enumerate(parts):
        if p == "*" and bi < len(bindings):
            parts[i] = str(bindings[bi])
            bi += 1
    return ".".join(parts)


def _compare(op: str, value: Any, threshold: Any) -> bool:
    if op == ">":
        return value > threshold
    if op == ">=":
        return value >= threshold
    if op == "<":
        return value < threshold
    if op == "<=":
        return value <= threshold
    if op == "==":
        return value == threshold
    if op == "!=":
        return value != threshold
    if op == "truthy":
        return bool(value)
    if op == "nonempty":
        return len(value) > 0
    if op == "min_len":
        return len(value) >= threshold
    if op == "subset":
        return set(value) <= set(threshold)
    if op == "in":
        return value in threshold
    raise ValueError(f"unknown guard op {op!r}")


def check_slos(doc: Any, guards: Sequence[SloGuard]) -> List[str]:
    """Evaluate every guard against ``doc``; returns violation messages
    (empty == all pass). Multi-match semantics are for-all: every metric
    match must satisfy the op against every resolved threshold."""
    violations: List[str] = []
    for g in guards:
        if g.when is not None:
            wpath, wop, wlit = g.when
            wmatches = resolve_path(doc, wpath)
            if not wmatches:
                violations.append(
                    f"{g.name}: when-path {wpath!r} missing from document")
                continue
            if not all(_compare(wop, v, wlit) for _, v in wmatches):
                continue  # gate not met — guard does not apply
        matches = resolve_path(doc, g.metric)
        if not matches:
            violations.append(
                f"{g.name}: no value at {g.metric!r}")
            continue
        for bindings, value in matches:
            if isinstance(g.threshold, Ref):
                rpath = _substitute(g.threshold.path, bindings)
                refs = [v for _, v in resolve_path(doc, rpath)]
                if not refs:
                    violations.append(
                        f"{g.name}: no threshold value at {rpath!r}")
                    continue
            else:
                refs = [g.threshold]
            for t in refs:
                try:
                    ok = _compare(g.op, value, t)
                except TypeError as e:
                    ok = False
                    violations.append(
                        f"{g.name}: {_substitute(g.metric, bindings)} "
                        f"uncomparable ({e})")
                    continue
                if not ok:
                    where = _substitute(g.metric, bindings) \
                        if bindings else g.metric
                    violations.append(
                        f"{g.name}: {where} = {value!r} violates "
                        f"{g.op} {t!r}")
    return violations
