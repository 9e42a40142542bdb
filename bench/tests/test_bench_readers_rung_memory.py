"""The reader of the engine's rung memory, ``rung_memory_hit_pct``, on a
synthetic window and in a traced run at a tiny scale."""
import os

import pytest

import bench_cpu
import cells_common as cc
from bench import loops
from bench.harness import Window, load_module


def _reader(name):
    return load_module(os.path.join(bench_cpu.ROOT, "bench", "metrics",
                                    name + ".py"), "t_" + name).read


def _span(name, t0, t1, **attrs):
    return type("S", (), {"name": name, "t0": t0, "t1": t1,
                          "attrs": attrs})()


def _window(spans):
    s = loops.Served([0.0] * 4, [0.1] * 4, {}, [], t0=0.0, t1=1.0,
                     t_end=1.0)
    return Window(1.0, 1.0, s, spans, {"escalations": 0}, None)


def test_rung_memory_hit_pct_counts_remembered_requests():
    read = _reader("rung_memory_hit_pct")
    # a program that does not mark remembered rungs reads nothing, not 0
    assert read(_window([])) is None
    assert read(_window([_span("dispatch", 0.0, 0.03, n=1)])) is None
    # one remembered request of the five dispatched
    w = _window([_span("dispatch", 0.0, 0.030, n=1, remembered=1),
                 _span("dispatch.launch", 0.0, 0.002),
                 _span("dispatch", 0.1, 0.110, n=4, remembered=0)])
    assert read(w) == pytest.approx(20.0)
    assert read(_window([_span("dispatch", 0.0, 0.02, n=3,
                               remembered=0)])) == 0.0


def test_traced_run_enters_q7_at_its_remembered_rung():
    res = cc.run_is_correct(bench_cpu.WORKLOAD, bench_cpu.ROOT, trace=True)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # Q7 climbs the ladder in the warm-up; in the window every Q7 enters at
    # the rung it needed there, and nothing climbs
    assert 0 < m["rung_memory_hit_pct"] < 100
    assert m["escalated_dispatch_pct"] == 0.0
    assert m["escalations_per_query"] == 0.0
