"""The program's spans in the benchmark: each new reader on a synthetic
run and on a ring that dropped spans, and a traced run of the small-file
cell at a tiny size."""
import time
from types import SimpleNamespace

import pytest

from chip_bench_cells import BENCH, imagenet_cell, run
from chipbench import programspans, registry, trace
from chipbench.runner import Run
from chipbench.spans import Spans
from test_chip_bench_trace import cpu_ops

from repro.fanstore.metrics import SPANS, SpanRecorder

NEW = ["read_plan_ms", "read_local_ms", "read_remote_ms",
       "loader_put_wait_ms", "read_account_ms"]


def _span(sid, name, start_ms, dur_ms, parent=None, **counters):
    return SimpleNamespace(id=sid, name=name, parent=parent,
                           start_ns=int(start_ms * 1e6),
                           end_ns=int((start_ms + dur_ms) * 1e6),
                           duration_ns=int(dur_ms * 1e6), counters=counters)


def _synthetic_run(trace_summary, window=(1.0, 2.0)):
    cell = imagenet_cell()
    return Run(cell=cell, setup_s=1.0, window_s=window[1] - window[0],
               step_ends=[1.0], samples=16, spans=Spans(), window=window,
               flops_per_sample=None, chip=None, trace=trace_summary)


@pytest.fixture
def synthetic(monkeypatch):
    spans = [
        _span(1, "fanstore.read_many", 900, 10, local_ns=2_000_000),  # before
        _span(9, "fanstore.read_many.remote", 902, 5, parent=1),
        _span(10, "fanstore.fetch.remote", 903, 2, parent=9,
              account_ns=5_000_000),
        _span(2, "fanstore.read_many", 1100, 10, local_ns=2_000_000),
        _span(3, "fanstore.read_many.remote", 1105, 5, parent=2),
        _span(7, "fanstore.fetch.remote", 1105, 2, parent=3,
              account_ns=300_000),
        _span(8, "fanstore.fetch.remote", 1107, 2, parent=3,
              account_ns=200_000),
        _span(4, "fanstore.read_many", 1200, 8, local_ns=4_000_000),
        _span(5, "fanstore.loader.put_wait", 1111, 0.5),
        _span(6, "fanstore.loader.put_wait", 1209, 1.5),
    ]
    ring = SimpleNamespace(
        dropped_start_ns=None,
        spans=lambda name: [s for s in spans if s.name == name])
    monkeypatch.setattr(programspans, "recorder", lambda: ring)
    return _synthetic_run(trace.TraceSummary(window_s=1.0, busy_s=0.2,
                                             devices=1))


@pytest.mark.parametrize("name,want", [
    ("read_plan_ms", (3 + 4) / 2),       # 10 - 2 - 5 and 8 - 4 - 0
    ("read_local_ms", (2 + 4) / 2),
    ("read_remote_ms", (5 + 0) / 2),
    ("loader_put_wait_ms", (0.5 + 1.5) / 2),
    ("read_account_ms", (0.3 + 0.2 + 0) / 2),
])
def test_reader_on_a_synthetic_run(synthetic, name, want):
    got = registry.metric_reader(BENCH, name).read(synthetic)
    assert got == pytest.approx(want, rel=1e-9)


def test_the_read_split_adds_up_to_the_read(synthetic):
    read = {n: registry.metric_reader(BENCH, n).read(synthetic)
            for n in ("read_plan_ms", "read_local_ms", "read_remote_ms")}
    assert sum(read.values()) == pytest.approx((10 + 8) / 2, rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_without_the_program_spans(monkeypatch, name):
    monkeypatch.setattr(programspans, "recorder", lambda: None)
    empty = _synthetic_run(trace.TraceSummary(window_s=1.0, busy_s=0.2,
                                              devices=1))
    untraced = _synthetic_run(None)
    reader = registry.metric_reader(BENCH, name)
    assert reader.read(empty) is None
    assert reader.read(untraced) is None


def _ring_that_dropped(monkeypatch, before: int, inside: int):
    """A ring of 4 that saw ``before`` read spans before the window opened
    and ``inside`` after; returns a run over that window."""
    rec = SpanRecorder(capacity=4)
    rec.forced = True
    for _ in range(before):
        with rec.span(programspans.READ):
            pass
    time.sleep(0.001)
    lo = time.perf_counter()
    time.sleep(0.001)
    for _ in range(inside):
        with rec.span(programspans.READ):
            pass
    monkeypatch.setattr(programspans, "recorder", lambda: rec)
    return _synthetic_run(None, window=(lo, time.perf_counter() + 1.0))


@pytest.mark.parametrize("name", NEW)
def test_readers_refuse_a_window_the_ring_dropped_spans_of(monkeypatch,
                                                           name):
    run_ = _ring_that_dropped(monkeypatch, before=1, inside=5)
    assert programspans.recorder().dropped == 2
    assert not programspans.whole(run_)
    assert registry.metric_reader(BENCH, name).read(run_) is None


def test_drops_before_the_window_leave_its_spans_whole(monkeypatch):
    run_ = _ring_that_dropped(monkeypatch, before=2, inside=4)
    assert programspans.recorder().dropped == 2
    assert programspans.whole(run_)
    assert len(programspans.within(run_, programspans.READ)) == 4
    assert registry.metric_reader(BENCH, "read_plan_ms").read(run_) >= 0


def test_an_untraced_run_leaves_the_recorder_empty():
    SPANS.clear()
    res = run(imagenet_cell())
    assert res["correct"], res["checks"]
    assert SPANS.spans() == [] and SPANS.dropped == 0


def test_a_traced_run_reports_the_split_and_its_sums():
    SPANS.clear()
    res = run(imagenet_cell(), trace=True, seconds=1.0,
              trace_select=cpu_ops)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m)
    reads = [s.duration_ns for s in SPANS.spans("fanstore.read_many")]
    mean_read_ms = 1e-6 * sum(reads) / len(reads)
    split = m["read_plan_ms"] + m["read_local_ms"] + m["read_remote_ms"]
    assert split == pytest.approx(mean_read_ms, rel=0.1)
    # the program's read nests inside the harness's span around it
    assert 0 < mean_read_ms <= m["read_many_ms"]
    assert 0 <= m["read_account_ms"] <= m["read_remote_ms"]
    assert m["loader_put_wait_ms"] >= 0
    assert SPANS.dropped == 0
    SPANS.clear()
