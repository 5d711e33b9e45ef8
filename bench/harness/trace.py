"""From the profiler's trace to the numbers the per-layer metrics read.

A traced run wraps its window in a host annotation ``bench.window`` and
the calls into each layer in annotations of their own (``engine.step``,
``engine.submit``, ``loadgen.wait``, ``trainer.run``). The reduction
keeps, within that window:

* device operations (``XLA Ops`` of each ``/device:`` plane) and the
  executables they ran in (``XLA Modules``);
* host events of every thread, so that the copy to the host
  (``np.asarray(jax.Array)``) and each idle gap of the device can be put
  down to what the host was doing.

:class:`Trace` is plain data and round-trips through JSON, which is how
the tests check the reduction on a small recorded trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import shutil
import tempfile

WINDOW = "bench.window"
# the host annotations the harness puts around calls into the program;
# an idle gap is named after the outermost of them and the innermost event
LAYER_SPANS = ("engine.step", "engine.submit", "loadgen.wait",
               "loadgen.record", "trainer.run")


@dataclasses.dataclass
class Trace:
    """Events in ns on the host's trace clock: ``(name, start, end)`` per
    device for ops and modules, per host thread for host events."""

    window: tuple
    ops: dict          # device plane name -> [(name, start, end)]
    modules: dict      # device plane name -> [(name, start, end)]
    host: dict         # thread name -> [(name, start, end)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def conv(m):
            return {k: [tuple(e) for e in v] for k, v in m.items()}
        return cls(window=tuple(d["window"]), ops=conv(d["ops"]),
                   modules=conv(d["modules"]), host=conv(d["host"]))


@contextlib.contextmanager
def profiled():
    """Trace the device and the host while the block runs; yields a dict
    that holds the reduced :class:`Trace` under ``"trace"`` afterwards.
    The raw trace goes to a temporary directory that is removed."""
    import jax

    out = {}
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    # no Python function tracing: it slows the host loop being measured
    # and buries the runtime's own events under frames
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file, found {files}")
        out["trace"] = from_xspace(files[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def from_xspace(path: str) -> Trace:
    """Read an ``.xplane.pb`` and keep what falls in the ``bench.window``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
                elif line.name == "XLA Modules":
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host[f"{plane.name}/{line.name}"] = _events(line)
    windows = [e for evs in host.values() for e in evs if e[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} event, found "
                           f"{len(windows)}")
    _, w0, w1 = windows[0]
    clip = lambda evs: [e for e in evs if e[2] > w0 and e[1] < w1]  # noqa
    return Trace(window=(w0, w1),
                 ops={k: clip(v) for k, v in ops.items()},
                 modules={k: clip(v) for k, v in modules.items()},
                 host={k: clip(v) for k, v in host.items() if clip(v)})


def _events(line) -> list:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _union(intervals, lo, hi) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(tr: Trace) -> float | None:
    """Seconds in which an operation ran, averaged over the devices."""
    if not tr.ops:
        return None
    lo, hi = tr.window
    per = [sum(e - s for s, e in _union([(s, e) for _, s, e in evs], lo, hi))
           for evs in tr.ops.values()]
    return sum(per) / len(per) / 1e9


def op_seconds(tr: Trace, top: int = 10) -> list:
    """``[[name, seconds], ...]`` of the device operations that took most
    time in the window, summed over devices."""
    lo, hi = tr.window
    acc = {}
    for evs in tr.ops.values():
        for name, s, e in evs:
            acc[name] = acc.get(name, 0) + min(e, hi) - max(s, lo)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def module_seconds(tr: Trace, match) -> float | None:
    """Device seconds of the executables whose name ``match`` accepts,
    averaged over devices; None when none ran."""
    lo, hi = tr.window
    per, found = [], False
    for evs in tr.modules.values():
        ivs = [(s, e) for name, s, e in evs if match(name)]
        found = found or bool(ivs)
        per.append(sum(e - s for s, e in _union(ivs, lo, hi)))
    if not found:
        return None
    return sum(per) / len(per) / 1e9


def host_seconds(tr: Trace, name: str) -> float | None:
    """Seconds, on any host thread, inside events called ``name``."""
    lo, hi = tr.window
    ivs = [(s, e) for evs in tr.host.values() for n, s, e in evs if n == name]
    if not ivs:
        return None
    return sum(e - s for s, e in _union(ivs, lo, hi)) / 1e9


def _main_thread(tr: Trace) -> list:
    for evs in tr.host.values():
        if any(n == WINDOW for n, _, _ in evs):
            return evs
    return []


def _host_segments(events, lo, hi) -> list:
    """``[(start, end, label)]`` covering the main thread in the window,
    labelled by the outermost layer span and the innermost event open
    there. Events on one thread nest, so a stack sweep finds them."""
    evs = sorted(((s, e, n) for n, s, e in events if n != WINDOW),
                 key=lambda x: (x[0], -x[1]))
    segs, stack, t = [], [], lo

    def label():
        outer = next((n for _, n in stack if n in LAYER_SPANS), None)
        inner = stack[-1][1] if stack else None
        if outer is None:
            return "other" if inner is None else inner
        return outer if inner == outer else f"{outer}/{inner}"

    def advance(to):
        nonlocal t
        while stack and stack[-1][0] <= to:
            end = stack[-1][0]
            if end > t:
                segs.append((t, end, label()))
                t = end
            stack.pop()
        if to > t:
            segs.append((t, to, label()))
            t = to

    for s, e, n in evs:
        if s >= hi:
            break
        advance(max(s, lo))
        stack.append((e, n))
    advance(hi)
    return [(s, e, lab) for s, e, lab in segs if e > s]


def idle_gaps(tr: Trace, top: int = 10) -> list:
    """``[[label, seconds], ...]``: device 0's idle time in the window,
    put down to what the main host thread was doing meanwhile."""
    if not tr.ops:
        return []
    lo, hi = tr.window
    evs = tr.ops[sorted(tr.ops)[0]]
    busy = _union([(s, e) for _, s, e in evs], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    acc = {}
    segs = _host_segments(_main_thread(tr), lo, hi)
    i = 0
    for gs, ge in gaps:
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, lab = segs[j]
            acc[lab] = acc.get(lab, 0) + min(e, ge) - max(s, gs)
            j += 1
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[lab, ns / 1e9] for lab, ns in ranked]


def save(tr: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
