"""95th percentile of the time between consecutive consumer steps, over
every step of the window (the first counted from the window's start)."""
import numpy as np


def read(run):
    gaps = np.diff(np.asarray([0.0] + run.step_ends))
    return float(np.percentile(gaps, 95)) * 1e3
