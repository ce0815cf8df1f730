"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device busy time is the union of the intervals in which an operation ran
on the device, clipped to the traced window; the idle share is one minus
busy over the window. Op time is self time: an op's duration less the
part of it its nested ops cover (a loop's time holds its body's), so the
top ops add up to the busy time. Each idle gap is named by the innermost
harness span (``bench.*``) on the host that covers its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from chipbench.spans import PREFIX

TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
TPU_OP_LINE = "XLA Ops"
WINDOW_SPAN = PREFIX + "window"
NAME_CHARS = 200                    # an op's HLO text is kept to this
Interval = Tuple[int, int]          # (start_ns, end_ns)


def tpu_ops(plane_name: str, line_name: str, event_name: str) -> bool:
    """Selects the device op events of a TPU trace."""
    return bool(TPU_PLANE.match(plane_name)) and line_name == TPU_OP_LINE


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                    # averaged over the device planes
    devices: int
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _subtract_children(events: List[Tuple[str, int, int]]) -> Dict[str, int]:
    """Self time by op name, for the events of one line, which nest."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[List] = []           # [name, end, start, covered by children]
    order = sorted(events, key=lambda x: (x[1], -x[2]))

    def close(item):
        name, end, start, covered = item
        out[name] += (end - start) - covered

    for name, s, e in order:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([name, e, s, 0])
    while stack:
        close(stack.pop())
    return out


def reduce_trace(path: str,
                 select: Callable[[str, str, str], bool] = tpu_ops,
                 top: int = 10) -> TraceSummary:
    """Busy time, idle share, top ops and named idle gaps of one trace.

    ``select(plane, line, event)`` picks the device op events; the window
    is the harness's ``bench.window`` span on the host.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    per_device: Dict[str, List[Interval]] = defaultdict(list)
    per_line: Dict[Tuple[str, str], List[Tuple[str, int, int]]] = \
        defaultdict(list)
    host: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if select(plane.name, line.name, ev.name):
                    if e > s:
                        per_device[plane.name].append((s, e))
                        per_line[(plane.name, line.name)].append(
                            (ev.name, s, e))
                elif ev.name.startswith(PREFIX) and e > s:
                    host.append((ev.name, s, e))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    if not per_device:
        raise ValueError(f"no device op in {path}")
    busy = {d: union(clip(iv, lo, hi)) for d, iv in per_device.items()}
    busy_ns = sum(sum(e - s for s, e in b) for b in busy.values()) / len(busy)

    ops: Dict[str, int] = defaultdict(int)
    for evs in per_line.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        for name, ns in _subtract_children(inside).items():
            ops[name] += ns
    n_dev = len(busy)
    device_ops = sorted(((n[:NAME_CHARS], ns / n_dev / 1e9)
                         for n, ns in ops.items()), key=lambda x: -x[1])[:top]

    gaps_by_name: Dict[str, int] = defaultdict(int)
    cover = _Cover([(n, s, e) for n, s, e in host if n != WINDOW_SPAN])
    for b in busy.values():
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps_by_name[cover((gs + ge) // 2)] += ge - gs
    idle_gaps = sorted(((n, ns / n_dev / 1e9)
                        for n, ns in gaps_by_name.items()),
                       key=lambda x: -x[1])[:top]
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9,
                        devices=n_dev, device_ops=device_ops,
                        idle_gaps=idle_gaps)


class _Cover:
    """Names a time by the innermost (shortest) harness span holding it."""

    def __init__(self, spans: List[Tuple[str, int, int]]):
        self.spans = sorted(spans, key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.spans]
        self.longest = max((e - s for _, s, e in spans), default=0)

    def __call__(self, t: int) -> str:
        best: Optional[Tuple[int, str]] = None
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i][1] >= t - self.longest:
            name, s, e = self.spans[i]
            if t < e and (best is None or e - s < best[0]):
                best = (e - s, name)
            i -= 1
        return best[1][len(PREFIX):] if best else "outside spans"
