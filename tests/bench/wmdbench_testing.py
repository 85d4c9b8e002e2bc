"""Helpers for the benchmark's tests: the path to `wmdbench`, and a tiny
checkout (its own BENCHMARK.json, configuration and traffic files, the
benchmark's metric readers) that the harness can run on the CPU.

The tiny checkout holds one tiny configuration per configuration of the
benchmark, ``tiny_<config>``: that configuration's own file (its service
block, ``v_r``, ``lamb``, iterations and laws) at the `TINY` sizes. Its
cells are the benchmark's own, renamed ``tiny_<config>.<traffic>``, and,
for every tiny configuration, the mixes of `EXTRA_MIXES` that the
benchmark does not have, so the harness's paths that no chip cell declares
yet stay tested: bulk pruned top-10 with the union rerank, open-loop
Poisson top-10 through the coalescer, and bulk full distances of
whole-document queries."""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY = {"vocab_size": 512, "embed_dim": 32, "num_docs": 96}
ZIPF_WORDS = 5             # words of a tiny Zipf query

_BULK_FULL = {"arrival": {"kind": "bulk"}, "request": {"kind": "full"},
              "queries": {"kind": "zipf", "s": 1.07, "words": ZIPF_WORDS},
              "service": {"window_ms": 2.0, "max_batch": 8},
              "writes": {"share": 0.0}, "check": {"queries": 16}}
EXTRA_MIXES = {
    "topk_bulk": dict(_BULK_FULL,
                      request={"kind": "top_k", "k": 10, "rerank": "union"},
                      check={"queries": 12}),
    "topk_open": dict(_BULK_FULL,
                      arrival={"kind": "poisson", "rate_per_s": 20.0,
                               "burst": None},
                      request={"kind": "top_k", "k": 10,
                               "rerank": "per_query"},
                      check={"queries": 24}),
    "documents_bulk": dict(_BULK_FULL, queries={"kind": "documents"}),
}


def load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f)


def _tiny_workloads(bm: dict) -> tuple[list[dict], dict[str, str]]:
    """The cells of the tiny checkout made from benchmark ``bm``, in order,
    and the tiny name of each of ``bm``'s own cells."""
    names = {w["name"]: f"tiny_{w['config']}.{w['traffic']}"
             for w in bm["workloads"]}
    cells = [dict(w, name=names[w["name"]], config=f"tiny_{w['config']}")
             for w in bm["workloads"]]
    for c in bm["configs"]:
        for mix in EXTRA_MIXES:
            if not any(w["config"] == c["name"] and w["traffic"] == mix
                       for w in bm["workloads"]):
                cells.append({"name": f"tiny_{c['name']}.{mix}",
                              "config": f"tiny_{c['name']}", "traffic": mix,
                              "chips": 1, "why": f"the harness's {mix} path"})
    return cells, names


def tiny_cells(source: str = REPO) -> list[str]:
    """The cells of the tiny checkout made from ``source``, in order."""
    cells, _ = _tiny_workloads(load(os.path.join(source, "BENCHMARK.json")))
    return [w["name"] for w in cells]


def tiny_root(dest: str, *, source: str = REPO,
              rate_per_s: float = 20.0) -> str:
    """A checkout at ``dest`` whose cells run the traffic mixes of the
    checkout at ``source`` on tiny corpora (V = 512, w = 32, 96 docs,
    5-word Zipf queries)."""
    src_bench = os.path.join(source, "bench")
    bench = os.path.join(dest, "bench")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "traffic"))
    shutil.copytree(os.path.join(src_bench, "metrics"),
                    os.path.join(bench, "metrics"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(src_bench, "peaks.json"), bench)
    bm = load(os.path.join(source, "BENCHMARK.json"))
    mixes = {w["traffic"]: load(os.path.join(src_bench, "traffic",
                                             f"{w['traffic']}.json"))
             for w in bm["workloads"]}
    for name, mix in EXTRA_MIXES.items():
        mixes.setdefault(name, copy.deepcopy(mix))
    for name, t in mixes.items():
        if t["queries"]["kind"] == "zipf":
            t["queries"] = dict(t["queries"], words=ZIPF_WORDS)
        if t["arrival"]["kind"] == "poisson":
            t["arrival"]["rate_per_s"] = rate_per_s
        _dump(t, os.path.join(bench, "traffic", f"{name}.json"))
    configs = []
    for c in bm["configs"]:
        cfg = load(os.path.join(source, c["file"]))
        tiny = f"tiny_{c['name']}"
        cfg.update(TINY, name=tiny)
        _dump(cfg, os.path.join(bench, "configs", f"{tiny}.json"))
        configs.append(dict(c, name=tiny, file=f"bench/configs/{tiny}.json"))
    bm["workloads"], names = _tiny_workloads(bm)
    bm["configs"] = configs
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [names[x] for x in m["workloads"]]
    _dump(bm, os.path.join(dest, "BENCHMARK.json"))
    return dest
