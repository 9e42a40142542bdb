"""A later change adds a cell, a traffic mix and a metric by adding files
and entries only: the harness finds them by name."""
import json
import os
import shutil

import bench_cpu

READER = '''"""Answers delivered in the window, whatever its traffic."""


def read(w):
    return sum(d is not None for d in w.served.delivered)
'''


def test_new_traffic_and_metric_are_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(bench_cpu.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(bench_cpu.ROOT, "BENCHMARK.json"), root)
    # a new traffic mix: four clients, three queries, three times each
    mix = json.load(open(root / "bench" / "traffic"
                         / "lubm-query-test.json"))
    mix.update(clients=4, repeat=3, queries=mix["queries"][:3])
    json.dump(mix, open(root / "bench" / "traffic" / "lubm-four.json", "w"))
    (root / "bench" / "metrics" / "answers_delivered.py").write_text(READER)
    spec = json.load(open(root / "BENCHMARK.json"))
    before = json.dumps(spec["workloads"])
    spec["workloads"].append({
        "name": "lubm50-four", "config": "lubm50", "traffic": "lubm-four",
        "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "answers_delivered", "unit": "queries", "better": "higher",
        "source": "host_clock", "layer": "load generator", "moves": "qps",
        "workloads": ["lubm50-four"]})
    json.dump(spec, open(root / "BENCHMARK.json", "w"))
    traffic = dict(bench_cpu.TINY_TRAFFIC, clients=4, repeat=3,
                   queries=mix["queries"])
    res = bench_cpu.run_tiny("lubm50-four", trace=True, root=str(root),
                             traffic=traffic)
    assert res["correct"]
    assert res["metrics"]["answers_delivered"]["value"] > 0
    assert set(res["metrics"]) == {"answers_delivered"}
    # the existing cells and files were left as they were
    assert json.dumps(spec["workloads"][:-1]) == before
