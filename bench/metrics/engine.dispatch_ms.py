"""Median duration of the program's ``serve.dispatch`` spans (upload,
call, wait for the device, copy to the host) that start in the window,
on the main thread."""
import numpy as np

from harness import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    durs = [e - s for n, s, e in trace._main_thread(run.trace)
            if n == "serve.dispatch" and lo <= s < hi]
    if not durs:
        return None
    return float(np.median(durs)) / 1e6
