"""The lubm50-query-test cell at a tiny scale on the CPU: every query's
answers equal the reference, in a plain and a traced run."""
import bench_cpu
import cells_common as cc

WORKLOAD = bench_cpu.WORKLOAD
ROOT = bench_cpu.ROOT


def test_every_query_equals_reference():
    _, rows = cc.queries_equal_reference(WORKLOAD, ROOT)
    # LUBM's answer sizes at Department0: a course's students, its members
    assert rows["Q1"] < rows["Q7"] < rows["Q5"]


def test_run_is_correct():
    res = cc.run_is_correct(WORKLOAD, ROOT)
    assert set(res["metrics"]) == {"qps", "setup_s"}


def test_traced_run_reads_every_per_layer_metric():
    res = cc.run_is_correct(WORKLOAD, ROOT, trace=True)
    assert {"submit_ms", "dispatch_ms",
            "escalations_per_query"} <= set(res["metrics"])
