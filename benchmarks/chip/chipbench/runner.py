"""One run of one cell: set-up, a measured window, the check, the metrics.

Set-up makes the inputs from the seed, builds the data plane and the
consumer, compiles and warms up every shape the window uses. The window
then runs whole consumer steps until ``seconds`` have passed, so it ends
on a step boundary. After it the data plane is closed, the device's peak
memory is read, the program's state is freed and the consumer compares
what the window produced with the plain reference.
"""
from __future__ import annotations

import gc
import pathlib
import resource
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from chipbench import gen, registry
from chipbench.peaks import Peaks, peaks
from chipbench.plane import Plane
from chipbench.spans import Spans
from chipbench.trace import TraceSummary, find_xplane, reduce_trace

CACHE_DIR = registry.BENCH_DIR / ".jax_cache"


@dataclass
class Check:
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: registry.Cell
    setup_s: float
    window_s: float
    step_ends: List[float]           # seconds after the window opened
    samples: int
    spans: Spans
    window: Tuple[float, float]      # host clock
    flops_per_sample: Optional[float]
    chip: Optional[Peaks]
    trace: Optional[TraceSummary] = None


class GcTimer:
    """Counts the collector's passes and the time they hold the process."""

    def __init__(self):
        self.passes = [0, 0, 0]
        self.seconds = 0.0
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.passes[info["generation"]] += 1

    def close(self) -> None:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)


def step_profile(step_ends: List[float], seconds: float) -> str:
    """Quantiles of the step gaps and the rate of each tenth of the window,
    for finding where a run's time went."""
    gaps = np.diff(np.asarray([0.0] + step_ends)) * 1e3
    q = np.percentile(gaps, [50, 95, 99, 100])
    tenths = np.bincount(np.minimum(
        (np.asarray(step_ends) * 10 / seconds).astype(int), 9), minlength=10)
    stalls = [(round(step_ends[i], 3), round(float(gaps[i]), 1))
              for i in np.flatnonzero(gaps > 250)]
    return (f"p50_ms={q[0]:.3f} p95_ms={q[1]:.3f} p99_ms={q[2]:.3f} "
            f"max_ms={q[3]:.3f} over_4x_p50={int((gaps > 4 * q[0]).sum())} "
            f"steps_per_tenth={tenths.tolist()} "
            f"stalls_over_250ms_at_s={stalls}")


class CompileCounter:
    """Counts the programs built, each compiled or read from the
    persistent cache, through JAX's monitoring events."""

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        # recorded around every compile-or-read-from-cache
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.programs - self.cache_hits


def enable_compile_cache(path: pathlib.Path = CACHE_DIR) -> None:
    """JAX's persistent cache at a fixed path in the checkout, for every
    program however short its compile, and never evicted: it holds this
    cell's programs alone, and a size cap meant for a shared cache (as
    ``JAX_COMPILATION_CACHE_MAX_SIZE`` may set) evicts a train step's
    programs before the next run can read them."""
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peak_memory_bytes(devices) -> Optional[int]:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def run(cell: registry.Cell, seed: int, seconds: float, trace: bool,
        t0: float, devices=None, trace_select=None, fault=None) -> Dict:
    """Runs ``cell`` once; returns the result line as a dict.

    ``t0`` is the host clock at the start of the process. ``devices``
    defaults to ``jax.devices()[:cell.chips]``; ``trace_select`` picks the
    device op events out of a trace (the TPU's by default). ``fault``, for
    the checks' own tests only, is called with the consumer before set-up
    and may break it.
    """
    devices = devices or jax.devices()[:cell.chips]
    dev = devices[0]
    enable_compile_cache()
    counter = CompileCounter()
    spans = Spans(enabled=False)

    t_gen = time.perf_counter()
    start_s = t_gen - t0
    model = cell.config.get("model", {})
    paths, files, tokens = gen.make_dataset(cell.config["dataset"], seed,
                                            vocab=model.get("vocab_size", 0))
    gen_s = time.perf_counter() - t_gen
    consumer = cell.consumer().Consumer(cell, paths, files, tokens, seed,
                                        devices)
    consumer_s = time.perf_counter() - t_gen - gen_s
    if fault is not None:
        fault(consumer)
    t_plane = time.perf_counter()
    plane = Plane(cell.config["topology"], cell.traffic, paths, files,
                  consumer.decode, spans, seed)
    plane_s = time.perf_counter() - t_plane
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    gc_timer = GcTimer()
    try:
        t_setup = time.perf_counter()
        consumer.setup(plane, spans)
        warm_s = time.perf_counter() - t_setup
        setup_compiles = (counter.compiled, counter.cache_hits)
        before = counter.programs
        spans.enabled = trace
        if trace:
            jax.profiler.start_trace(trace_dir)
        step_ends: List[float] = []
        samples = 0
        gc_timer.passes, gc_timer.seconds = [0, 0, 0], 0.0
        with spans.span("window"):
            t_w0 = time.perf_counter()
            wall_w0 = time.time()
            while True:
                with spans.step(len(step_ends)):
                    batch = plane.next(spans)
                    with spans.span("step"):
                        samples += consumer.step(batch)
                t = time.perf_counter() - t_w0
                step_ends.append(t)
                if t >= seconds:
                    break
            t_w1 = time.perf_counter()
        gc_timer.close()
        if trace:
            jax.profiler.stop_trace()
        spans.enabled = False
        window_compiles = counter.programs - before
    finally:
        gc_timer.close()
        spans.enabled = False
        plane.close()
    memory_peak = peak_memory_bytes(devices)
    setup_s = t_w0 - t0
    log(f"setup: setup_s={setup_s} start_s={start_s} gen_s={gen_s} "
        f"consumer_s={consumer_s} plane_s={plane_s} warm_s={warm_s} "
        f"files={plane.report.num_files} bytes={plane.report.input_bytes} "
        f"compiled={setup_compiles[0]} cache_hits={setup_compiles[1]}")
    log(f"window: steps={len(step_ends)} samples={samples} "
        f"window_s={t_w1 - t_w0} programs_in_window={window_compiles} "
        f"memory_peak_bytes={memory_peak} "
        f"host_rss_peak_bytes={resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}")
    log(f"steps: {step_profile(step_ends, seconds)} "
        f"gc_passes={gc_timer.passes} gc_s={gc_timer.seconds} "
        f"window_opened_unix_s={wall_w0}")

    t_check = time.perf_counter()
    checks, attempted, failed = consumer.check()
    log(f"check: check_s={time.perf_counter() - t_check}")
    summary = None
    if trace:
        try:
            summary = reduce_trace(find_xplane(trace_dir),
                                   **({"select": trace_select}
                                      if trace_select else {}))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    chip = peaks(dev.device_kind) if dev.platform == "tpu" else None
    rec = Run(cell=cell, setup_s=setup_s, window_s=t_w1 - t_w0,
              step_ends=step_ends, samples=samples, spans=spans,
              window=(t_w0, t_w1),
              flops_per_sample=getattr(consumer, "flops_per_sample", None),
              chip=chip, trace=summary)
    metrics = {}
    for m in cell.metrics(trace):
        value = registry.metric_reader(cell.bench_dir, m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(step_ends) and all(c.ok for c in checks.values()),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["checks"] = {name: {"value": c.value, "limit": c.limit}
                        for name, c in checks.items()}
    for name, c in checks.items():
        log(f"check {name}: {c.value} limit {c.limit} "
            f"{'ok' if c.ok else 'FAILED'}")
    return result
