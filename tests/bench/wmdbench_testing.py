"""Helpers for the benchmark's tests: the path to `wmdbench`, and a tiny
checkout (its own BENCHMARK.json, configuration and traffic files, the
benchmark's metric readers) that the harness can run on the CPU.

Besides the benchmark's own cells, the tiny checkout has two top-k mixes
that no chip cell declares yet, so the harness's top-k and open-loop paths
stay tested: bulk pruned top-10 with the union rerank, and open-loop
Poisson top-10 through the coalescer."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY = {"name": "tiny", "vocab_size": 512, "embed_dim": 32, "num_docs": 96,
        "nnz_max": 8, "v_r": 8, "max_iter": 5,
        "doc_words": {"law": "lognormal", "mean": 6, "sigma": 0.55,
                      "min": 3, "max": 7}}


TOPK_MIXES = {
    "topk_bulk": {"arrival": {"kind": "bulk"},
                  "request": {"kind": "top_k", "k": 10, "rerank": "union"},
                  "check": {"queries": 12}},
    "topk_open": {"arrival": {"kind": "poisson", "rate_per_s": 20.0,
                              "burst": None},
                  "request": {"kind": "top_k", "k": 10,
                              "rerank": "per_query"},
                  "check": {"queries": 24}},
}


def load(path):
    with open(path) as f:
        return json.load(f)


def tiny_root(dest: str, *, rate_per_s: float = 20.0) -> str:
    """A checkout at ``dest`` whose cells run the real traffic mixes on a
    tiny corpus (V = 512, w = 32, 96 docs, 5-word queries)."""
    bench = os.path.join(dest, "bench")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "traffic"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench)
    cfg = load(os.path.join(BENCH, "configs", "paper_5k.json"))
    cfg.update(TINY)
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    bm = load(os.path.join(REPO, "BENCHMARK.json"))
    mixes = {w["traffic"]: load(os.path.join(BENCH, "traffic",
                                             f"{w['traffic']}.json"))
             for w in bm["workloads"]}
    base = load(os.path.join(BENCH, "traffic", "full_bulk.json"))
    for name, mix in TOPK_MIXES.items():
        mixes.setdefault(name, dict(base, **mix))
    for name, t in mixes.items():
        t["queries"] = dict(t["queries"], words=5)
        if t["arrival"]["kind"] == "poisson":
            t["arrival"]["rate_per_s"] = rate_per_s
        with open(os.path.join(bench, "traffic", f"{name}.json"), "w") as f:
            json.dump(t, f)
    names = {}
    for w in bm["workloads"]:
        names[w["name"]] = f"tiny.{w['traffic']}"
        w["name"], w["config"] = names[w["name"]], "tiny"
    for name in TOPK_MIXES:
        if f"tiny.{name}" not in names.values():
            bm["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                    "traffic": name, "chips": 1,
                                    "why": "the harness's top-k path"})
    bm["configs"] = [dict(bm["configs"][0], name="tiny",
                          file="bench/configs/tiny.json")]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [names[x] for x in m["workloads"]]
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return dest
