"""The trace reduction, on a trace this test records on the CPU itself."""
import time

import jax
import jax.numpy as jnp
import pytest

from chip_bench_cells import BENCH  # noqa: F401  (puts chipbench on the path)
from chipbench import trace
from chipbench.spans import Spans


def cpu_ops(plane, line, event):
    """XLA's CPU client runs each op as an event on its own host thread."""
    return (plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")
            and "::" not in event and not event.startswith("end:"))


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert trace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_self_time_subtracts_nested_ops():
    ops = trace._subtract_children([("loop", 0, 100), ("body", 10, 40),
                                    ("body", 50, 80), ("leaf", 20, 30),
                                    ("after", 100, 120)])
    assert ops == {"loop": 40, "body": 50, "leaf": 10, "after": 20}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    spans = Spans(enabled=True)
    jax.profiler.start_trace(d)
    with spans.span("window"):
        for i in range(3):
            with spans.step(i):
                with spans.span("input_wait"):
                    time.sleep(0.02)
                with spans.span("step"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.reduce_trace(trace.find_xplane(d), select=cpu_ops), spans


def test_reduction_reads_busy_idle_ops_and_gaps(recorded):
    summary, spans = recorded
    (w0, w1), = spans.records["window"]
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(w1 - w0, rel=0.05)
    assert 0 < summary.busy_s < summary.window_s
    assert 0.5 < summary.idle_share < 1.0        # three sleeps of 20 ms
    assert summary.device_ops and all(s > 0 for _, s in summary.device_ops)
    assert sum(s for _, s in summary.device_ops) == pytest.approx(
        summary.busy_s, rel=1e-6)
    names = dict(summary.idle_gaps)
    assert names["input_wait"] >= 0.055            # the sleeps
    assert sum(names.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_a_trace_without_the_window_span_is_refused(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError):
        trace.reduce_trace(trace.find_xplane(str(tmp_path)), select=cpu_ops)
