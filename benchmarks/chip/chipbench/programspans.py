"""Per-layer numbers from the program's own spans (``fanstore.*``).

The program records them in ``repro.fanstore.metrics.SPANS`` while the
profiler runs (a traced run's window), on ``time.perf_counter_ns``, the
clock of the run's window. A program without that recorder gives no
number, and neither does a run that recorded no span of the name, nor
one whose ring dropped a span that started in the window (its mean would
cover only the later part of the window).
"""
from collections import defaultdict
from statistics import fmean
from typing import Dict, List, Optional

READ = "fanstore.read_many"
REMOTE = "fanstore.read_many.remote"
FETCH = "fanstore.fetch.remote"


def recorder():
    """The program's span recorder, or None in a program without one."""
    try:
        from repro.fanstore.metrics import SPANS
    except ImportError:
        return None
    return SPANS


def recorded(name: str) -> Optional[List]:
    """Every recorded span of ``name``, or None without a recorder."""
    rec = recorder()
    return None if rec is None else rec.spans(name)


def whole(run) -> bool:
    """False where the recorder's ring dropped a span that started at or
    after the window opened. Read after the spans: a later drop only
    makes the answer stricter."""
    start = getattr(recorder(), "dropped_start_ns", None)
    return start is None or start < int(run.window[0] * 1e9)


def within(run, name: str) -> List:
    """Spans of ``name`` that started inside the run's window; none where
    the ring lost any of them."""
    spans = recorded(name) or ()
    if not whole(run):
        return []
    lo, hi = (int(t * 1e9) for t in run.window)
    return [s for s in spans if lo <= s.start_ns <= hi]


def mean_ms(values) -> Optional[float]:
    values = list(values)
    return 1e-6 * fmean(values) if values else None


def remote_ns(reads: List) -> Dict[int, int]:
    """Each read's remote leg (its ``fanstore.read_many.remote`` child)."""
    ids = {r.id for r in reads}
    return {s.parent: s.duration_ns for s in recorded(REMOTE) or ()
            if s.parent in ids}


def account_ns(reads: List) -> Dict[int, int]:
    """Each read's ``account_ns``, summed over the per-owner
    ``fanstore.fetch.remote`` spans inside its remote leg."""
    ids = {r.id for r in reads}
    leg = {s.id: s.parent for s in recorded(REMOTE) or () if s.parent in ids}
    out: Dict[int, int] = defaultdict(int)
    for s in recorded(FETCH) or ():
        if s.parent in leg:
            out[leg[s.parent]] += s.counters.get("account_ns", 0)
    return out
