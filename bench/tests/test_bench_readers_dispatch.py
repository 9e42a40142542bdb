"""The readers of the engine's dispatch phases: ``launch_ms``,
``fetch_ms``, ``fetch_bytes``, ``deliver_ms`` and
``escalated_dispatch_pct``, on a synthetic window and in a traced run at a
tiny scale."""
import os

import pytest

import bench_cpu
import cells_common as cc
from bench import loops
from bench.harness import Window, load_module

PHASE_METRICS = {"launch_ms", "fetch_ms", "fetch_bytes", "deliver_ms",
                 "escalated_dispatch_pct"}


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


def _dispatch_window():
    """Two dispatches with their phases: one request, escalated, then a
    clean batch of four."""
    return _window([
        _span("dispatch", 0.0, 0.030, n=1, escalated=1),
        _span("dispatch.launch", 0.0, 0.002),
        _span("dispatch.wait", 0.002, 0.028),
        _span("dispatch.fetch", 0.028, 0.030, bytes=600_000),
        _span("deliver", 0.030, 0.031, n=1),
        _span("dispatch", 0.1, 0.110, n=4, escalated=0),
        _span("dispatch.launch", 0.1, 0.104),
        _span("dispatch.wait", 0.104, 0.109),
        _span("dispatch.fetch", 0.109, 0.110, bytes=200_000),
        _span("deliver", 0.110, 0.113, n=4)])


def test_launch_ms_reads_the_launch_spans():
    assert _reader("launch_ms")(_window([])) is None
    assert _reader("launch_ms")(_dispatch_window()) == pytest.approx(3.0)


def test_fetch_ms_reads_the_fetch_spans():
    assert _reader("fetch_ms")(_window([])) is None
    assert _reader("fetch_ms")(_dispatch_window()) == pytest.approx(1.5)


def test_fetch_bytes_reads_the_fetch_spans_bytes():
    assert _reader("fetch_bytes")(_window([])) is None
    assert _reader("fetch_bytes")(_dispatch_window()) == pytest.approx(
        400_000)


def test_deliver_ms_reads_the_deliver_spans():
    assert _reader("deliver_ms")(_window([])) is None
    assert _reader("deliver_ms")(_dispatch_window()) == pytest.approx(2.0)


def test_escalated_dispatch_pct_weighs_dispatch_time():
    read = _reader("escalated_dispatch_pct")
    # a program that does not mark escalations reads nothing, not 0
    assert read(_window([_span("dispatch", 0.0, 0.03, n=1)])) is None
    # 30 ms of the 40 ms belonged to the request thrown away
    assert read(_dispatch_window()) == pytest.approx(75.0)
    half = _window([_span("dispatch", 0.0, 0.02, n=2, escalated=1)])
    assert read(half) == pytest.approx(50.0)


def test_traced_run_reads_the_phase_metrics():
    res = cc.run_is_correct(bench_cpu.WORKLOAD, bench_cpu.ROOT, trace=True)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert PHASE_METRICS <= set(m)
    # Q7 climbs the escalation ladder in every cycle of the query test
    assert 0 < m["escalated_dispatch_pct"] < 100
    assert m["launch_ms"] + m["fetch_ms"] < m["dispatch_ms"]
