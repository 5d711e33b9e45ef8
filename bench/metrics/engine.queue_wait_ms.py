"""Median wait from admission to dispatch, from the engine's own request
timelines (the last 4096 requests its timeline store keeps)."""
import numpy as np


def read(run):
    w = run.queue_wait_s
    if w is None or len(w) == 0:
        return None
    return float(np.median(w)) * 1e3
