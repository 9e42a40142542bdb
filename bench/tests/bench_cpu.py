"""Helpers for the benchmark's CPU tests: a cell run at a tiny scale
through the harness, with the chip check skipped."""
from __future__ import annotations

import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

WORKLOAD = "lubm50-query-test"


def tiny_config() -> dict:
    """LUBM(1,0) cut to two departments, with degrees from four
    universities (so that University0 has alumni), and an engine whose
    batch buckets stop at 4, so that the CPU compiles few programs."""
    with open(os.path.join(ROOT, "bench", "configs", "lubm50.json")) as f:
        profile = json.load(f)["profile"]
    return {"universities": 1,
            "profile": dict(profile, departments=[2, 2],
                            degree_universities=4),
            "engine": {"max_batch": 4}}


TINY_TRAFFIC = {"warmup_rounds": 1, "check_sample": 10_000, "repeat": 2}


def cpu_device(chips: int) -> dict:
    import jax
    from bench.device import describe
    return describe(jax.devices()[:chips])


def run_tiny(workload: str = WORKLOAD, seed: int = 5, seconds: float = 1.0,
             trace: bool = False, root: str = ROOT, **kw) -> dict:
    from bench.harness import run
    return run(root, workload, seed, seconds, trace, time.perf_counter(),
               cpu_device, overrides=tiny_config(),
               traffic_overrides=kw.pop("traffic", TINY_TRAFFIC),
               out=io.StringIO(), err=io.StringIO(), **kw)
