"""BENCHMARK.json against the benchmark's contract, and discovery: a new
configuration, traffic mix or metric is only new files and new entries."""
import json
import os
import re
import shutil
import time
import types

from wmdbench_testing import BENCH, REPO, load, tiny_cells, tiny_root

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


def _check_cells(bm, root):
    """The rules every cell is held to, whichever cells there are."""
    cells = [w["name"] for w in bm["workloads"]]
    assert 1 <= len(cells) <= 24 and len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in bm["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert NAME.fullmatch(w["name"]) and _line(w["why"])
        assert NAME.fullmatch(w["traffic"]) and w["chips"] in (1, 4)
        assert w["config"] in {c["name"] for c in bm["configs"]}
        assert os.path.exists(os.path.join(root, "bench", "traffic",
                                           f"{w['traffic']}.json"))
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(cells) // 2)
    for c in bm["configs"]:
        assert any(w["config"] == c["name"] for w in bm["workloads"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells)
    for m in bm["per_layer"]:
        assert m["workloads"], m["name"]


def test_configs_and_cells():
    assert 1 <= len(BM["configs"]) <= 24
    assert len({c["name"] for c in BM["configs"]}) == len(BM["configs"])
    assert len({c["file"] for c in BM["configs"]}) == len(BM["configs"])
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and _line(c["why"])
        assert _line(c["source"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("bench/configs/")
        cfg = load(os.path.join(REPO, c["file"]))
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))
    _check_cells(BM, REPO)


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
    """Two configurations, two mixes (one of whole-document queries), two
    cells and a metric, added by files and BENCHMARK.json entries alone;
    the second configuration, with its own v_r, lamb and document law,
    runs its documents cell to ``correct`` in the tiny harness."""
    import jax
    from wmdbench import cell
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    _copy_checkout(root)
    before = {p: open(p, "rb").read() for p in _files(root)}
    cfg = load(os.path.join(BENCH, "configs", "paper_5k.json"))
    _write(root, "configs/paper_5k_kcache.json",
           dict(cfg, name="paper_5k_kcache",
                service={"cache_capacity": 4096}))
    _write(root, "configs/short_docs.json",
           dict(cfg, name="short_docs", v_r=16, lamb=0.5,
                doc_words={"law": "lognormal", "mean": 12, "sigma": 0.5,
                           "min": 3, "max": 40}))
    t = load(os.path.join(BENCH, "traffic", "full_bulk.json"))
    _write(root, "traffic/full_q4.json",
           dict(t, service=dict(t["service"], max_batch=4)))
    _write(root, "traffic/documents_bulk.json",
           dict(t, queries={"kind": "documents"}))
    with open(os.path.join(root, "bench", "metrics", "kcache_hits.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    bm = load(os.path.join(root, "BENCHMARK.json"))
    bm["configs"] += [
        {"name": "paper_5k_kcache", "source": "x",
         "file": "bench/configs/paper_5k_kcache.json", "reduced": [],
         "why": "K cache on"},
        {"name": "short_docs", "source": "x",
         "file": "bench/configs/short_docs.json", "reduced": [],
         "why": "short documents, v_r 16, lambda 0.5"}]
    bm["workloads"] += [
        {"name": "paper_5k_kcache.full_q4", "config": "paper_5k_kcache",
         "traffic": "full_q4", "chips": 1, "why": "Q=4 buckets"},
        {"name": "short_docs.documents_bulk", "config": "short_docs",
         "traffic": "documents_bulk", "chips": 1,
         "why": "whole-document queries"}]
    for m in bm["end_to_end"]:
        if m["name"] == "full_qps":
            m["workloads"] += ["paper_5k_kcache.full_q4",
                               "short_docs.documents_bulk"]
    kcache = {"name": "kcache_hits.bulk", "unit": "%", "better": "higher",
              "source": "program_counter", "layer": "K cache",
              "moves": "full_qps", "workloads": ["paper_5k_kcache.full_q4"]}
    bm["per_layer"].append(kcache)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    _check_cells(bm, root)
    entry = spec.cell(bm, "paper_5k_kcache.full_q4")
    assert spec.config(bm, entry, root)["service"] == {
        "cache_capacity": 4096}
    assert spec.traffic(entry, root)["service"]["max_batch"] == 4
    ctx = types.SimpleNamespace(
        spans=[], registry={}, prune=[], trace=None,
        traffic=spec.traffic(entry, root))
    got = report.metrics(bm, "paper_5k_kcache.full_q4", ctx, True, root)
    assert got == {"kcache_hits.bulk": {"value": 42.0, "unit": "%"}}
    assert "kcache_hits.bulk" not in {
        m["name"] for m in spec.cell_metrics(bm, "paper_5k.full_bulk",
                                             "per_layer")}
    # a metric with no workloads goes wherever its end-to-end metric is
    bare = {k: v for k, v in kcache.items() if k != "workloads"}
    assert spec.applies(bare, "paper_5k.full_bulk", bm)
    assert spec.applies(bare, "short_docs.documents_bulk", bm)
    after = {p: open(p, "rb").read() for p in before}
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [os.path.join(root, "BENCHMARK.json")]
    # the tiny harness takes the new cells as they are
    tiny = tiny_root(str(tmp_path / "tiny"), source=root)
    assert "tiny_short_docs.documents_bulk" in tiny_cells(root)
    tbm = spec.load_benchmark(tiny)
    name = "tiny_short_docs.documents_bulk"
    tcfg = spec.config(tbm, spec.cell(tbm, name), tiny)
    assert (tcfg["v_r"], tcfg["lamb"], tcfg["doc_words"]["mean"]) == \
        (16, 0.5, 12)
    r = cell.run(tbm, name, seed=2**31 + 41, seconds=0.3, trace=False,
                 devices=jax.devices(), t_start=time.perf_counter(),
                 root=tiny)
    line = report.result_line(tbm, name, r, False, jax.devices(), tiny)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"full_qps", "setup_s"}
    words = {m for b in r["ctx"].batch_words for m in b}
    assert len(words) > 1 and max(words) <= 16


def _write(root, rel, obj):
    with open(os.path.join(root, "bench", rel), "w") as f:
        json.dump(obj, f)


def _files(root):
    for d, _, fs in os.walk(root):
        for f in fs:
            yield os.path.join(d, f)
