"""The program's own names in a trace (`wmdbench.progtrace`), and the
per-layer metrics that read the program's spans and counters."""
import json
import os
import time
import types

import pytest

from wmdbench_testing import BENCH, tiny_root

from wmdbench import progtrace, spec

RECORDED = os.path.join(BENCH, "testdata", "full_bulk_1s.xplane.pb")
RECORDED_NAMES = os.path.join(BENCH, "testdata", "full_bulk_op_names.json")
SOLVE = "jit(per_device)/"


def _ev(name, s, e):
    return types.SimpleNamespace(name=name, start_ns=float(s),
                                 duration_ns=float(e - s))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[_ev(*x) for x in evs])
        for ln, evs in lines.items()])


def _trace(ops, host=(), module=("jit_per_device(3)", 0, 200)):
    tpu = _plane("/device:TPU:0", {"XLA Modules": [module],
                                   "XLA Ops": ops})
    host = _plane("/host:CPU", {"python": [("wmdbench.window", 0, 300),
                                           *host]})
    return types.SimpleNamespace(planes=[host, tpu])


# (event name as a TPU trace has it, start, end)
OPS = [
    ("%fusion.0 = f32[8,32,100000] fusion(%p0)", 0, 20),
    ("%while = (s32[], f32[8,32,5000]) while(%t)", 20, 120),
    ("%fusion.1 = f32[720000,8,32] fusion(%p1)", 25, 60),
    ("%fusion.2 = f32[8,32,5000] fusion(%p2)", 60, 110),
    ("%fusion.3 = f32[720000,8,32] fusion(%p3)", 120, 170),
    ("%copy-done.1 = f32[8,32,100001] copy-done(%c)", 170, 180),
]
NAMES = {"fusion.0": SOLVE + "wmd.precompute/exp",
         "while": SOLVE + "wmd.iterate/while",
         "fusion.1": SOLVE + "wmd.iterate/while/body/wmd.gather/gather",
         "fusion.2": SOLVE + "wmd.iterate/while/body/dot_general",
         "fusion.3": SOLVE + "wmd.final/wmd.gather/gather"}


def test_scopes_take_self_time_by_outermost_scope():
    """A ``while`` counts only what its body does not; each op goes to its
    outermost scope, gathers again under ``wmd.gather``, and an op with
    no scope to ``(none)``; phases and ``(none)`` add up to the ops."""
    t = progtrace.reduce(_trace(OPS), NAMES)
    s = t["scopes"]["jit_per_device"]
    assert s == {"wmd.precompute": pytest.approx(20e-9),
                 "wmd.iterate": pytest.approx(100e-9),
                 "wmd.final": pytest.approx(50e-9),
                 "wmd.gather": pytest.approx(85e-9),
                 progtrace.NONE: pytest.approx(10e-9)}
    assert sum(v for k, v in s.items() if k != progtrace.GATHER) == \
        pytest.approx(180e-9)


def test_self_times_clip_to_the_window():
    ev = [(n, s, e) for n, s, e in [("outer", 0, 100), ("inner", 40, 60)]]
    got = {x: ns for x, ns in progtrace.self_times(ev, 50, 100)}
    assert got == {"outer": 40, "inner": 10}


def test_op_names_from_compiled_hlo():
    hlo = ('  %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
           'metadata={op_name="jit(per_device)/wmd.final/mul" '
           'source_file="x.py"}\n'
           '  ROOT %while.2 = (s32[]) while(%t), condition=%c, body=%b, '
           'metadata={op_name="jit(per_device)/wmd.iterate/while"}\n'
           '  %copy-done.1 = f32[8]{0} copy-done(%c)\n')
    assert progtrace.op_names(hlo) == {
        "fusion.9": "jit(per_device)/wmd.final/mul",
        "while.2": "jit(per_device)/wmd.iterate/while"}


def test_spans_count_seconds_and_idle():
    host = [("wmd.query_batch", 0, 250), ("wmd.dispatch", 0, 5),
            ("wmd.fetch", 5, 240), ("wmdbench.query_batch", 0, 251)]
    t = progtrace.reduce(_trace(OPS, host), NAMES)
    sp = t["spans"]
    assert set(sp) == {"wmd.query_batch", "wmd.dispatch", "wmd.fetch"}
    assert sp["wmd.query_batch"] == {"count": 1,
                                     "seconds": pytest.approx(250e-9),
                                     "idle_s": pytest.approx(70e-9)}
    assert sp["wmd.fetch"]["idle_s"] == pytest.approx(60e-9)
    assert sp["wmd.dispatch"]["idle_s"] == pytest.approx(0.0)


def test_raises_when_the_solve_carries_no_scope():
    """Device time in the solve module with no ``wmd.`` scope anywhere
    fails the reduction; another module may go unscoped."""
    with pytest.raises(RuntimeError, match="jit_per_device"):
        progtrace.reduce(_trace(OPS), {})
    other = progtrace.reduce(_trace(OPS, module=("jit_other", 0, 200)), {})
    assert other["scopes"] == {
        "jit_other": {progtrace.NONE: pytest.approx(180e-9)}}


def _ctx(registry):
    return types.SimpleNamespace(registry=registry)


def test_readers_on_a_synthetic_registry():
    reg = {"wmd_query_slots_total{kind=real}": 152.0,
           "wmd_query_slots_total{kind=pad}": 104.0,
           "wmd_ell_slots_total{kind=real}": 172_000.0,
           "wmd_ell_slots_total{kind=pad}": 548_000.0,
           "wmd_span_seconds{span=wmd.query_batch}": {"count": 4,
                                                      "sum": 5.6},
           "wmd_span_seconds{span=wmd.fetch}": {"count": 4, "sum": 5.58}}
    read = {m: spec.reader(m) for m in ("query_slot_use.full",
                                        "ell_slot_use.full",
                                        "service_host_ms.full")}
    assert read["query_slot_use.full"](_ctx(reg)) == pytest.approx(59.375)
    assert read["ell_slot_use.full"](_ctx(reg)) == \
        pytest.approx(100 * 172 / 720)
    assert read["service_host_ms.full"](_ctx(reg)) == pytest.approx(5.0)
    # a program without the counters and spans reads nothing
    assert all(r(_ctx({})) is None for r in read.values())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("name", ["tiny_paper_5k.full_bulk",
                                  "tiny_paper_5k.documents_bulk"])
def test_a_traced_tiny_run_reads_the_program(root, name):
    """A traced full-distance run on the CPU, of Zipf queries and of
    whole-document queries of mixed lengths: the slot shares equal the
    hand counts of the window's batches, warm-up left out, and the span
    histogram counts one ``wmd.query_batch`` per batch."""
    import jax
    from wmdbench import cell, gen
    bm = spec.load_benchmark(root)
    seed = 2**31 + 99
    r = cell.run(bm, name, seed=seed, seconds=0.3, trace=True,
                 devices=jax.devices(), t_start=time.perf_counter(),
                 root=root)
    ctx = r["ctx"]
    assert ctx.trace is not None            # the CPU trace has no TPU plane
    got = {m: {"value": spec.reader(m, root)(ctx)}
           for m in ("query_slot_use.full", "ell_slot_use.full",
                     "service_host_ms.full")}
    cfg = ctx.config
    b = int(ctx.traffic["service"]["max_batch"])
    words = sum(sum(w) for w in ctx.batch_words)
    assert got["query_slot_use.full"]["value"] == pytest.approx(
        100 * words / (ctx.batches * b * cfg["v_r"]))
    slots = gen.make_corpus(cfg, int(cfg["num_docs"]), seed).cols.size
    assert ctx.registry["wmd_ell_slots_total{kind=real}"] == \
        ctx.batches * ctx.nnz
    assert ctx.registry["wmd_ell_slots_total{kind=pad}"] == \
        ctx.batches * (slots - ctx.nnz)
    assert got["ell_slot_use.full"]["value"] == pytest.approx(
        100 * ctx.nnz / slots)
    root_span = ctx.registry["wmd_span_seconds{span=wmd.query_batch}"]
    assert root_span["count"] == ctx.batches
    assert 0 < got["service_host_ms.full"]["value"] < \
        1e3 * root_span["sum"] / root_span["count"]


def test_scopes_of_the_recorded_chip_trace():
    """The recorded paper_5k.full_bulk window on a TPU v5e (op names from
    the same run's compiled solve): the Sinkhorn loop is most of the
    solve, nearly every op carries a scope, the phases add up to the
    module's time, and the service's spans hold the idle time that the
    harness puts under its own ``wmdbench.query_batch``."""
    from wmdbench import devtrace
    pd = devtrace.load(RECORDED)
    with open(RECORDED_NAMES) as f:
        t = progtrace.reduce(pd, json.load(f))
    s = t["scopes"]["jit_per_device"]
    solve = sum(v for k, v in s.items() if k != progtrace.GATHER)
    assert s["wmd.iterate"] > 0.5 * solve
    assert s.get(progtrace.NONE, 0.0) <= 0.02 * solve
    dt = devtrace.reduce(pd)
    assert solve == pytest.approx(
        dt["modules"]["jit_per_device"]["seconds"], rel=0.01)
    assert t["spans"]["wmd.query_batch"]["idle_s"] >= \
        0.9 * dict(dt["idle_by_host"])["wmdbench.query_batch"]
