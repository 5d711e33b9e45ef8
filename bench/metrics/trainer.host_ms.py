"""The trainer's serial host work per step: for each of the program's
``train.step`` spans that start in the window, on the main thread, its
duration less that of the ``train.sync`` span inside it (the wait for the
step and the copy of its metrics), then the mean. The device waits on
this work between steps."""
from harness import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    evs = trace._main_thread(run.trace)
    steps = sorted((s, e) for n, s, e in evs
                   if n == "train.step" and lo <= s < hi)
    if not steps:
        return None
    syncs = sorted((s, e) for n, s, e in evs if n == "train.sync")
    host, j = [], 0
    for s, e in steps:
        while j < len(syncs) and syncs[j][0] < s:
            j += 1
        inside = 0
        while j < len(syncs) and syncs[j][1] <= e:
            inside += syncs[j][1] - syncs[j][0]
            j += 1
        host.append(e - s - inside)
    return sum(host) / len(host) / 1e6
