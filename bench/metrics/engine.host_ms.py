"""Host time of the serving engine per dispatch: the union of the
program's ``serve.schedule``, ``serve.pack`` and ``serve.slice`` spans on
the main thread in the window, over the dispatches the window completed."""
from harness import trace

SPANS = ("serve.schedule", "serve.pack", "serve.slice")


def read(run):
    if run.trace is None or not run.dispatches:
        return None
    lo, hi = run.trace.window
    ivs = [(s, e) for n, s, e in trace._main_thread(run.trace) if n in SPANS]
    if not ivs:
        return None
    ns = sum(e - s for s, e in trace._union(ivs, lo, hi))
    return ns / 1e6 / sum(run.dispatches.values())
