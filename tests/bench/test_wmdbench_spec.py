"""BENCHMARK.json against the benchmark's contract, and discovery: a new
configuration, traffic mix or metric is only new files and new entries."""
import json
import os
import re
import shutil
import types

from wmdbench_testing import BENCH, REPO, load

from wmdbench import report, spec

BM = load(os.path.join(REPO, "BENCHMARK.json"))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench", "tests/bench"]
    for p in BM["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    rs = BM["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with 24 cells fits its time
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_and_cells():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and _line(c["why"])
        assert c["file"].startswith("bench/configs/")
        cfg = load(os.path.join(REPO, c["file"]))
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))
        assert any(w["config"] == c["name"] for w in BM["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert [w["name"] for w in BM["workloads"]] == ["paper_5k.full_bulk"]
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))


def test_metrics():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(spec.reader(m["name"]))
    for w in BM["workloads"]:
        e = {m["name"] for m in spec.cell_metrics(BM, w["name"],
                                                  "end_to_end")}
        assert "setup_s" in e and len(e) >= 2
        layer = spec.cell_metrics(BM, w["name"], "per_layer")
        assert layer and all(m["moves"] in e for m in layer)


def _copy_checkout(dest):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(dest, "bench", sub))
    shutil.copy(os.path.join(BENCH, "peaks.json"),
                os.path.join(dest, "bench"))


def test_new_config_traffic_and_metric_are_files_only(tmp_path):
    root = str(tmp_path)
    _copy_checkout(root)
    before = {p: open(p, "rb").read() for p in _files(root)}
    cfg = load(os.path.join(BENCH, "configs", "paper_5k.json"))
    cfg.update(name="paper_5k_kcache", service={"cache_capacity": 4096})
    with open(os.path.join(root, "bench", "configs",
                           "paper_5k_kcache.json"), "w") as f:
        json.dump(cfg, f)
    t = load(os.path.join(BENCH, "traffic", "full_bulk.json"))
    t["service"]["max_batch"] = 4
    with open(os.path.join(root, "bench", "traffic", "full_q4.json"),
              "w") as f:
        json.dump(t, f)
    with open(os.path.join(root, "bench", "metrics", "kcache_hits.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    bm = load(os.path.join(root, "BENCHMARK.json"))
    bm["configs"].append({"name": "paper_5k_kcache", "source": "x",
                          "file": "bench/configs/paper_5k_kcache.json",
                          "reduced": [], "why": "K cache on"})
    bm["workloads"].append({"name": "paper_5k_kcache.full_q4",
                            "config": "paper_5k_kcache",
                            "traffic": "full_q4", "chips": 1,
                            "why": "Q=4 buckets"})
    for m in bm["end_to_end"]:
        if m["name"] == "full_qps":
            m["workloads"].append("paper_5k_kcache.full_q4")
    bm["per_layer"].append({"name": "kcache_hits.bulk", "unit": "%",
                            "better": "higher", "source": "program_counter",
                            "layer": "K cache", "moves": "full_qps"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    entry = spec.cell(bm, "paper_5k_kcache.full_q4")
    assert spec.config(bm, entry, root)["service"] == {
        "cache_capacity": 4096}
    assert spec.traffic(entry, root)["service"]["max_batch"] == 4
    ctx = types.SimpleNamespace(
        spans=[], registry={}, prune=[], trace=None,
        traffic=spec.traffic(entry, root))
    got = report.metrics(bm, "paper_5k_kcache.full_q4", ctx, True, root)
    assert got == {"kcache_hits.bulk": {"value": 42.0, "unit": "%"}}
    # a metric with no workloads goes wherever its end-to-end metric is
    assert "kcache_hits.bulk" in {
        m["name"] for m in spec.cell_metrics(bm, "paper_5k.full_bulk",
                                             "per_layer")}
    after = {p: open(p, "rb").read() for p in before}
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [os.path.join(root, "BENCHMARK.json")]


def _files(root):
    for d, _, fs in os.walk(root):
        for f in fs:
            yield os.path.join(d, f)
