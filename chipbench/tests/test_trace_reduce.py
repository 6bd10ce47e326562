"""The reduction from a profiler trace to busy time, program times and
named idle gaps: on hand-made planes, and on the small trace recorded
on one v5e chip by ``record_small_trace.py`` (two annotated "queries",
each a tiny program, a 20 ms sleep, and the program again)."""
import os
from types import SimpleNamespace as NS

import pytest

import trace_reduce

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_union_counts_overlap_once_and_names_gaps():
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("a", 0, 100), ev("b", 50, 100),   # 0..150
                         ev("c", 1000, 50)],                  # 1000..1050
                XLA_Modules=[ev("jit_f(12345)", 0, 150),
                             ev("jit_g(7)", 1000, 50)])
    host = plane("/host:CPU", python=[
        ev("chipbench.collect.q1", 100, 1200),
        ev("PjitFunction(g)", 400, 500)])
    r = trace_reduce.reduce_planes([dev, host], 1)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["n_device_ops"] == 3
    assert r["device_ops"] == [["jit_f", pytest.approx(150e-9)],
                               ["jit_g", pytest.approx(50e-9)]]
    assert r["idle_gaps"] == [
        ["chipbench.collect.q1 | PjitFunction(g)", pytest.approx(850e-9)]]


def test_busy_is_the_mean_over_the_chips_used():
    planes = [plane(f"/device:TPU:{i}", XLA_Ops=[ev("a", 0, 100 * (i + 1))])
              for i in range(4)]
    r = trace_reduce.reduce_planes(planes, 4)
    assert r["busy_s"] == pytest.approx((100 + 200 + 300 + 400) / 4 * 1e-9)
    assert r["busy_s_by_device"] == pytest.approx([1e-7, 2e-7, 3e-7, 4e-7])


def test_a_trace_without_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([plane("/host:CPU", python=[])], 1)
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(
            [plane("/device:TPU:0", XLA_Ops=[])], 1)


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData
    r = trace_reduce.reduce_file(SMALL, 1)
    # the same union by counting open intervals at each boundary
    planes = ProfileData.from_file(SMALL).planes
    ops = [ln for p in planes if p.name == "/device:TPU:0"
           for ln in p.lines if ln.name == "XLA Ops"][0]
    marks = sorted([(e.start_ns, 1) for e in ops.events] +
                   [(e.start_ns + e.duration_ns, -1) for e in ops.events],
                   key=lambda m: (m[0], -m[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in marks:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    # as read on the chip when it was recorded (my chip run, PR 25)
    assert r["busy_s"] == pytest.approx(8.4592e-05, rel=1e-6)
    assert r["n_device_ops"] == 14
    assert [name for name, _ in r["device_ops"]] == ["jit__lambda"]
    assert r["device_ops"][0][1] == pytest.approx(8.4625e-05, rel=1e-6)
    # the two 20 ms sleeps under chipbench.collect.* are the long gaps
    top = dict((k.split(" | ")[0], v) for k, v in r["idle_gaps"])
    assert top["chipbench.collect.a"] == pytest.approx(0.0219, abs=2e-3)
    assert top["chipbench.collect.b"] == pytest.approx(0.0207, abs=2e-3)
