"""The reduction from a profiler trace to busy and idle time, program time
by module name, top ops and idle time by host annotation."""
import os
import types

import pytest

from wmdbench_testing import BENCH

from wmdbench import devtrace

RECORDED = os.path.join(BENCH, "testdata", "full_bulk_1s.xplane.pb")


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=float(s),
                                  duration_ns=float(e - s))
            for n, s, e in evs])
        for ln, evs in lines.items()])


def test_reduce_a_hand_made_trace():
    tpu = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_per_device(12)", 0, 100), ("jit_x", 150, 200)],
        "XLA Ops": [("fusion.1", 0, 40), ("fusion.2", 50, 100),
                    ("copy", 150, 200)]})
    host = _plane("/host:CPU", {"python": [
        ("wmdbench.window", 0, 300), ("wmdbench.query_batch", 0, 120),
        ("wmdbench.top_k_batch", 140, 210), ("PjitFunction(f)", 0, 5)]})
    other = _plane("/device:TPU:0 SparseCore", {"XLA Ops": [("x", 0, 300)]})
    t = devtrace.reduce(types.SimpleNamespace(planes=[host, tpu, other]))
    assert t["devices"] == 1
    assert t["window_s"] == pytest.approx(300e-9)
    assert t["busy_s"] == pytest.approx(140e-9)
    assert t["modules"] == {
        "jit_per_device": {"count": 1, "seconds": pytest.approx(100e-9)},
        "jit_x": {"count": 1, "seconds": pytest.approx(50e-9)}}
    ops = dict(t["top_ops"])
    assert ops == {"jit_per_device:fusion.1": pytest.approx(40e-9),
                   "jit_per_device:fusion.2": pytest.approx(50e-9),
                   "jit_x:copy": pytest.approx(50e-9)}
    idle = dict(t["idle_by_host"])
    assert idle == {"wmdbench.query_batch": pytest.approx(30e-9),
                    "wmdbench.top_k_batch": pytest.approx(20e-9),
                    devtrace.IDLE_OUTSIDE: pytest.approx(110e-9)}
    assert sum(idle.values()) == pytest.approx(
        t["window_s"] - t["busy_s"])


def test_nested_annotations_take_the_innermost():
    tpu = _plane("/device:TPU:0", {"XLA Ops": [("op", 0, 10)]})
    host = _plane("/host:CPU", {"t": [
        ("wmdbench.window", 0, 100), ("wmdbench.outer", 10, 90),
        ("wmdbench.inner", 20, 30)]})
    t = devtrace.reduce(types.SimpleNamespace(planes=[tpu, host]))
    assert dict(t["idle_by_host"]) == {
        "wmdbench.outer": pytest.approx(70e-9),
        "wmdbench.inner": pytest.approx(10e-9),
        devtrace.IDLE_OUTSIDE: pytest.approx(10e-9)}


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace")
def test_reduce_the_recorded_chip_trace():
    """A one-second paper_5k.full_bulk window recorded on a TPU v5e: the
    solve program is found by its module name and fills the device."""
    t = devtrace.reduce(devtrace.load(RECORDED))
    assert t["devices"] == 1
    assert 0 < t["busy_s"] <= t["window_s"]
    assert "jit_per_device" in t["modules"]
    solve = t["modules"]["jit_per_device"]["seconds"]
    assert solve > 0.5 * t["busy_s"]
    assert sum(s for _, s in t["idle_by_host"]) == pytest.approx(
        t["window_s"] - t["busy_s"], rel=1e-6)


def test_solve_reader_raises_when_its_module_is_missing():
    """A window that dispatched batches but whose trace holds no solve
    module fails the run instead of dropping the metric."""
    from wmdbench import spec
    mod = spec.metric_module("solve_device_ms")
    trace = {"modules": {"jit_other": {"count": 3, "seconds": 1.0}}}
    ctx = types.SimpleNamespace(trace=trace, batches=3)
    with pytest.raises(RuntimeError, match="jit_other"):
        mod.read(ctx)
    trace["modules"]["jit_per_device"] = {"count": 3, "seconds": 0.3}
    assert mod.read(ctx) == pytest.approx(100.0)
