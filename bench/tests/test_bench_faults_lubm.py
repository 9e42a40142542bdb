"""The lubm50-query-test cell at a tiny scale on the CPU: the control and
each fault a one-chip serving cell can have come out not correct."""
import bench_cpu
import cells_common as cc

WORKLOAD = bench_cpu.WORKLOAD
ROOT = bench_cpu.ROOT


def test_control_fails():
    cc.control_fails(WORKLOAD, ROOT)


def test_altered_answer_fails():
    cc.altered_answer_fails(WORKLOAD, ROOT)


def test_half_batch_dropped_fails(monkeypatch):
    cc.half_batch_dropped_fails(WORKLOAD, ROOT, monkeypatch)
