"""The command refuses to run without a TPU; peaks are keyed by chip."""
import json
import os
import subprocess
import sys

import pytest

import bench_cpu
from bench import device


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(bench_cpu.ROOT, "bench", "run.py"),
         "--workload", bench_cpu.WORKLOAD, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=bench_cpu.ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_peaks_by_device_kind():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_benchmark_json_names_resolve():
    """Every cell's configuration, traffic and metric has its file."""
    from bench.harness import load_spec, metrics_for
    root = bench_cpu.ROOT
    spec = load_spec(root)
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(root, c["file"]))
        cfg = json.load(open(os.path.join(root, c["file"])))
        assert os.path.isfile(os.path.join(root, "bench", "gen",
                                           cfg["generator"] + ".py"))
    for wl in spec["workloads"]:
        assert os.path.isfile(os.path.join(root, "bench", "traffic",
                                           wl["traffic"] + ".json"))
        for trace in (False, True):
            ms = metrics_for(spec, wl["name"], trace)
            assert ms
            for m in ms:
                assert os.path.isfile(os.path.join(
                    root, "bench", "metrics", m["name"] + ".py"))
