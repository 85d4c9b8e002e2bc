"""Where the benchmark finds its parts: everything is found by name.

* a cell: an entry of ``workloads`` in ``BENCHMARK.json``;
* its configuration: the ``file`` of the ``configs`` entry it names;
* its traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, or, for a split name
  such as ``rerank_ms_per_query.open``, ``bench/metrics/<base>.py`` where
  ``<base>`` is the part before the first dot. The module defines
  ``read(ctx)``, which returns a number or None when it finds nothing;
* the peaks of a device: ``bench/peaks.json``, keyed by ``device_kind``.

Adding a configuration, a traffic mix or a metric is adding its file and
its entry in ``BENCHMARK.json``; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

from wmdbench import gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class SpecError(ValueError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload named {name!r} in BENCHMARK.json")


def config(bm: dict, cell_entry: dict, root: str = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == cell_entry["config"]:
            return load_json(os.path.join(root, c["file"]))
    raise SpecError(f"no config named {cell_entry['config']!r}")


def traffic(cell_entry: dict, root: str = ROOT) -> dict:
    """The cell's traffic mix. Queries come from one of
    `gen.QUERY_SOURCES`. The schema also carries bursts and writes, which
    the generator does not drive yet: a mix that asks for one, or for
    another query source, is refused, not ignored."""
    path = os.path.join(root, "bench", "traffic",
                        f"{cell_entry['traffic']}.json")
    if not os.path.exists(path):
        raise SpecError(f"no traffic file {path}")
    t = load_json(path)
    if t["arrival"].get("burst"):
        raise SpecError(f"{path}: bursts are not implemented yet")
    if t["queries"]["kind"] not in gen.QUERY_SOURCES:
        raise SpecError(f"{path}: query source {t['queries']['kind']!r} "
                        f"is not implemented yet")
    if float(t.get("writes", {}).get("share", 0.0)) > 0:
        raise SpecError(f"{path}: writes are not implemented yet")
    return t


def applies(metric: dict, cell_name: str, bm: dict) -> bool:
    """Does ``metric`` belong in ``cell_name``'s result line? A metric with
    ``workloads`` lists its cells; a per-layer metric without one goes
    wherever the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if "moves" in metric:
        for m in bm["end_to_end"]:
            if m["name"] == metric["moves"]:
                return applies(m, cell_name, bm)
        return False
    return True


def cell_metrics(bm: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics of one cell."""
    return [m for m in bm[kind] if applies(m, cell_name, bm)]


def metric_module(name: str, root: str = ROOT):
    """The module of metric ``name``: ``bench/metrics/<name>.py``, else
    ``bench/metrics/<base>.py``."""
    base = name.split(".", 1)[0]
    metrics_dir = os.path.join(root, "bench", "metrics")
    for stem in dict.fromkeys((name, base)):
        path = os.path.join(metrics_dir, f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"wmdbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise SpecError(f"no reader for metric {name!r} in {metrics_dir}")


def reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of metric ``name``."""
    return metric_module(name, root).read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    table = load_json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]
