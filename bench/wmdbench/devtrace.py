"""Profiler trace of the measured window, and its reduction to metrics.

`capture` runs the window under `jax.profiler` (no Python tracer, host
annotations only) and reads the ``.xplane.pb`` back with
`jax.profiler.ProfileData`. `reduce` turns it into plain numbers:

* busy seconds: the union of the intervals of the device's ``XLA Ops``
  events inside the window, averaged over the TPU planes;
* the window: the ``wmdbench.window`` host annotation the harness puts
  around the measured loop;
* program time: the device durations of ``XLA Modules`` events, by module
  name (the jitted function's name, without the ``(id)`` suffix);
* the top device ops by total duration, labelled ``module:op``;
* idle time by what the host was doing: each gap between busy intervals,
  split over the ``wmdbench.*`` annotations that cover it (innermost
  first), and ``host: outside any service call`` for the rest.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import tempfile

WINDOW_SPAN = "wmdbench.window"
SPAN_PREFIX = "wmdbench."
IDLE_OUTSIDE = "host: outside any service call"
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


@contextlib.contextmanager
def capture(result: dict):
    """Profile the enclosed block; on exit ``result["trace"]`` holds the
    reduced trace and ``result["xplane_bytes"]`` the raw size."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory(prefix="wmdbench-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        result["xplane_bytes"] = os.path.getsize(paths[0])
        result["trace"] = reduce(load(paths[0]))


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _union(intervals):
    """Sorted, merged [(start, end)] of ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce(pd) -> dict:
    """Reduce a `ProfileData` to the numbers the metric readers use.

    Times are seconds; ``modules`` maps module name to ``{"count",
    "seconds"}`` summed over devices; ``busy_s`` is the mean over devices.
    """
    devices = [p for p in pd.planes
               if re.fullmatch(r"/device:TPU:\d+", p.name)]
    spans = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    op_sets = [list(_events(p, "XLA Ops")) for p in devices]
    if window:
        lo, hi = window[0]
    else:
        allev = [x for ops in op_sets for x in ops]
        lo = min((s for _, s, _ in allev), default=0.0)
        hi = max((e for _, _, e in allev), default=0.0)
    window_s = (hi - lo) * 1e-9
    busy = []
    modules: dict[str, dict] = {}
    op_time: dict[str, float] = {}
    for p, ops in zip(devices, op_sets):
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(merged)
        mods = sorted((s, e, module_name(n))
                      for n, s, e in _events(p, "XLA Modules"))
        for s, e, name in mods:
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += (ce - cs) * 1e-9
        # label each op with the module whose execution contains it
        j = 0
        for n, s, e in sorted(ops, key=lambda x: x[1]):
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            while j < len(mods) and mods[j][1] < s:
                j += 1
            owner = mods[j][2] if j < len(mods) and mods[j][0] <= s \
                else "?"
            key = f"{owner}:{n}"
            op_time[key] = op_time.get(key, 0.0) + (ce - cs) * 1e-9
    busy_s = (sum(sum(e - s for s, e in b) for b in busy) / len(busy)
              * 1e-9) if busy else 0.0
    idle = _attribute_idle(busy[0] if busy else [], lo, hi,
                           [x for x in spans if x[0] != WINDOW_SPAN])
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": busy_s,
        "modules": modules,
        "top_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:10],
        "idle_by_host": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
    }


def _attribute_idle(busy, lo, hi, spans) -> dict[str, float]:
    """Seconds of device idle time inside [lo, hi], by the innermost
    (shortest) host annotation covering each instant: one sweep over the
    boundaries of the gaps and the annotations."""
    import heapq
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted((s, e, name) for name, s, e in spans if e > lo and s < hi)
    cuts = sorted({lo, hi, *(x for g in gaps for x in g),
                   *(min(max(x, lo), hi) for s, e, _ in spans
                     for x in (s, e))})
    out: dict[str, float] = {}
    active: list = []               # (duration, end, name)
    gi = si = 0
    for a, b in zip(cuts, cuts[1:]):
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi == len(gaps) or gaps[gi][0] > a:
            continue                # the device is busy here
        while si < len(spans) and spans[si][0] <= a:
            s, e, name = spans[si]
            heapq.heappush(active, (e - s, e, name))
            si += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)   # innermost already ended
        # an enclosing span may have ended under a live inner one
        name = active[0][2] if active else IDLE_OUTSIDE
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out
