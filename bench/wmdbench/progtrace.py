"""The program's own names in a profiler trace, reduced to plain numbers.

The program names its solve's device phases with ``jax.named_scope``
(``wmd.precompute``, ``wmd.iterate``, ``wmd.final``, and ``wmd.gather``
for the K gathers inside them), which lands in the op names of the
compiled program, and its service's host stages with
``jax.profiler.TraceAnnotation`` spans (``wmd.query_batch`` and its
children). `reduce` reads both off a `jax.profiler.ProfileData`, in the
window that `wmdbench.devtrace` uses:

* ``scopes``: per module, the device **self** time of each ``XLA Ops``
  event (its duration less the ops nested under it on its line, so a
  ``while`` does not count its body twice), summed by the outermost
  ``wmd.*`` scope of the op's name, plus ``wmd.gather`` wherever that
  scope appears, and ``(none)`` for an op with no ``wmd.`` scope. The
  phases and ``(none)`` add up to the module's op time; ``wmd.gather``
  is a part of them, counted again.
* ``spans``: per ``wmd.*`` host span, its ``count``, its ``seconds`` and
  ``idle_s``, the device idle time inside it (the first device, as
  `devtrace` attributes idle).

A TPU op event is named by its HLO instruction (``%fusion.27 = ...``).
The trace file keeps each op's name as a ``tf_op`` stat of the event's
metadata, which `ProfileData` does not expose, so `reduce` takes the map
from instruction name to op name that `op_names` builds from the solve's
compiled HLO text (``jax.jit(f).lower(...).compile().as_text()``).
"""
from __future__ import annotations

import re

from wmdbench import devtrace, spec

SCOPE_PREFIX = "wmd."
GATHER = "wmd.gather"
NONE = "(none)"
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+) = .*?'
                     r'metadata=\{[^}]*op_name="([^"]*)"', re.M)


def solve_modules() -> tuple[str, ...]:
    """The solve program's module names: those `solve_device_ms` reads."""
    return tuple(spec.metric_module("solve_device_ms").MODULES)


def op_names(hlo: str) -> dict[str, str]:
    """HLO instruction name -> ``op_name`` metadata, from compiled HLO
    text."""
    return dict(_HLO_OP.findall(hlo))


def scope_of(op_name: str | None) -> tuple[str, bool]:
    """(outermost ``wmd.*`` scope or ``(none)``, whether ``wmd.gather``
    is among its scopes) of an op name such as
    ``jit(per_device)/wmd.iterate/while/body/wmd.gather/gather``."""
    parts = (op_name or "").split("/")
    scopes = [p for p in parts if p.startswith(SCOPE_PREFIX)]
    return (scopes[0] if scopes else NONE), GATHER in scopes


def self_times(events, lo: float, hi: float):
    """[(event, self ns)] of one line's events clipped to [lo, hi]: each
    event's clipped duration less that of the events directly nested in
    it."""
    clipped = sorted(((max(s, lo), min(e, hi), ev) for ev, s, e in events
                      if e > lo and s < hi), key=lambda x: (x[0], -x[1]))
    out, stack = [], []                 # stack: [start, end, index]
    for s, e, ev in clipped:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][2]][1] -= e - s
        out.append([ev, e - s])
        stack.append((s, e, len(out) - 1))
    return out


def _window(pd):
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events
             if ev.name == devtrace.WINDOW_SPAN]
    return spans[0] if spans else None


def reduce(pd, names: dict[str, str]) -> dict:
    """``{"scopes": {module: {scope: seconds}}, "spans": {name: {"count",
    "seconds", "idle_s"}}}`` of a `ProfileData`, with ``names`` from
    `op_names` (see the module docstring). Raises when a solve module has
    op time and none of it carries a ``wmd.`` scope: the names have moved,
    and the phases must not fall silent."""
    devices = [p for p in pd.planes
               if re.fullmatch(r"/device:TPU:\d+", p.name)]
    ops_by_dev = [[(ev, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in p.lines if line.name == "XLA Ops"
                   for ev in line.events] for p in devices]
    window = _window(pd)
    if window is None:
        allev = [x for ops in ops_by_dev for x in ops]
        window = (min((s for _, s, _ in allev), default=0.0),
                  max((e for _, _, e in allev), default=0.0))
    lo, hi = window
    scopes: dict[str, dict[str, float]] = {}
    for p, ops in zip(devices, ops_by_dev):
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       devtrace.module_name(ev.name))
                      for line in p.lines if line.name == "XLA Modules"
                      for ev in line.events)
        j = 0
        for ev, ns in sorted(self_times(ops, lo, hi),
                             key=lambda x: x[0].start_ns):
            s = ev.start_ns
            while j < len(mods) and mods[j][1] < s:
                j += 1
            mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
            # "%fusion.27 = f32[...] fusion(...)" -> "fusion.27"
            op = names.get(ev.name.lstrip("%").split(" ", 1)[0])
            scope, gather = scope_of(op)
            by = scopes.setdefault(mod, {})
            for key in (scope, GATHER) if gather and scope != GATHER \
                    else (scope,):
                by[key] = by.get(key, 0.0) + ns * 1e-9
    for mod in solve_modules():
        by = scopes.get(mod, {})
        if sum(by.values()) > 0 and set(by) == {NONE}:
            raise RuntimeError(
                f"progtrace: module {mod} ran {sum(by.values()):.6f} s on "
                f"the device and none of its ops carries a "
                f"{SCOPE_PREFIX!r} scope")
    busy = devtrace._union(devtrace._clip(
        [(s, e) for _, s, e in ops_by_dev[0]], lo, hi)) if devices else []
    spans: dict[str, dict] = {}
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if not ev.name.startswith(SCOPE_PREFIX):
                    continue
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e <= s:
                    continue
                covered = sum(max(0.0, min(b, e) - max(a, s))
                              for a, b in busy)
                sp = spans.setdefault(ev.name, {"count": 0, "seconds": 0.0,
                                                "idle_s": 0.0})
                sp["count"] += 1
                sp["seconds"] += (e - s) * 1e-9
                sp["idle_s"] += (e - s - covered) * 1e-9
    return {"scopes": scopes, "spans": spans}
