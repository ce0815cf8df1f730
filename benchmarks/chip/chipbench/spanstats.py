"""Per-layer numbers from the harness's host spans."""
from typing import Optional


def mean_ms(run, name: str) -> Optional[float]:
    """Mean length of the spans ``name`` that started in the window."""
    spans = run.spans.within(name, *run.window)
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
