"""The result of one run: the metrics by reader, the device, the breakdown
of the trace, and the numbers compared beside their limits."""
from __future__ import annotations

import json
import sys

import numpy as np

from wmdbench import spec


def metrics(bm: dict, cell_name: str, ctx, trace: bool,
            root: str = spec.ROOT) -> dict:
    """Every metric of the cell for this kind of run, each read by its own
    reader; a reader that finds nothing leaves its metric out."""
    kind = "per_layer" if trace else "end_to_end"
    out = {}
    for m in spec.cell_metrics(bm, cell_name, kind):
        value = spec.reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(bm: dict, cell_name: str, r: dict, trace: bool,
                devices, root: str = spec.ROOT) -> dict:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": r["memory_peak_bytes"]}
    line = {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"],
            "metrics": metrics(bm, cell_name, r["ctx"], trace, root)}
    t = r["ctx"].trace
    if t is not None:
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
    line["device"] = device
    if t is not None:
        line["breakdown"] = {"device_ops": [list(x) for x in t["top_ops"]],
                             "idle_gaps": [list(x)
                                           for x in t["idle_by_host"]]}
    line["checks"] = r["checks"]
    return line


def _pct(x, q):
    return float(np.percentile(x, q)) if len(x) else None


def log_lines(cell_name: str, seed: int, r: dict) -> list[str]:
    """What stderr carries: the run's set-up, the window's load and its
    compiles, then the numbers compared, last."""
    out = r["out"]
    late = out.get("lateness_ms")
    info = {
        "cell": cell_name, "seed": seed, "setup_s": r["setup_s"],
        "setup_compiles": r["setup_compiles"].compiles,
        "setup_persistent_hits": r["setup_compiles"].persistent_hits,
        "window_compiles": r["window_compiles"].compiles,
        "window_wall_s": out["wall_s"], "attempted": r["attempted"],
        "answered": len(out["answers"]), "failed": r["failed"],
        "query_clip_share": r["query_clip_share"],
        "generator_late_p95_ms": _pct(late, 95) if late is not None else None,
        "generator_late_max_ms": float(np.max(late))
        if late is not None and len(late) else None,
        "xplane_bytes": r["xplane_bytes"], "checked_queries": r["checked"],
        "check_s": r["check_s"],
    }
    lines = ["[wmdbench] " + json.dumps(info)]
    if out.get("errors"):
        lines.append("[wmdbench] errors: " + json.dumps(
            dict(list(out["errors"].items())[:5])))
    for name, c in r["checks"].items():
        lines.append(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return lines


def emit(line: dict, logs: list[str]) -> None:
    for s in logs:
        print(s, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
