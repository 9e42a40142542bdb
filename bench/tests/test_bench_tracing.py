"""The reduction from a profiler trace to device busy and idle time."""
import json
import os

import pytest

import bench_cpu  # noqa: F401
from bench import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_on_a_synthetic_trace():
    ms = 1_000_000
    host = [("bench/step", 0, 10 * ms), ("bench/wait", 10 * ms, 10 * ms),
            ("serve_dispatch/t0b4", 1 * ms, 6 * ms)]
    ops = [("fusion.1", 2 * ms, 2 * ms), ("fusion.2", 3 * ms, 2 * ms),
           ("copy", 12 * ms, 1 * ms), ("outside", 30 * ms, 5 * ms)]
    r = tracing.reduce(ops, host)
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.004)          # 2-5 ms and 12-13 ms
    ops_by = dict(r["device_ops"])
    assert ops_by["fusion.1"] == pytest.approx(0.002)
    assert "outside" not in ops_by
    gaps = dict(r["idle_gaps"])
    # 0-2 ms: midpoint 1 ms lies in serve_dispatch (1-7 ms) -> innermost
    # 5-12 ms: midpoint 8.5 ms in bench/step only
    assert gaps["serve_dispatch/t0b4"] == pytest.approx(0.002)
    assert gaps["bench/step"] == pytest.approx(0.007)
    assert gaps["bench/wait"] == pytest.approx(0.007)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_op_name():
    assert tracing.op_name("%while.28 = (u32[], s32[4]) while((u32[]) "
                           "%tuple.1), condition=%c") == "while.28"
    assert tracing.op_name("fusion.3") == "fusion.3"


def test_reduce_finds_nothing_to_read():
    assert tracing.reduce([], [("bench/step", 0, 10)]) is None
    assert tracing.reduce([("op", 0, 5)], []) is None


def test_reduce_on_a_recorded_chip_trace():
    """A short trace recorded on one TPU v5e chip: a tiny LUBM store served
    by the engine under the benchmark's host annotations."""
    with open(os.path.join(DATA, "trace_v5e_sample.json")) as f:
        rec = json.load(f)
    r = tracing.reduce(rec["ops"], rec["host"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx(rec["expect"]["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(" = " not in name for name, _ in r["device_ops"])
