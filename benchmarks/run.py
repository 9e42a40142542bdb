"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines and, per suite, writes a
machine-readable ``BENCH_<suite>.json`` next to this file (name ->
microseconds + parsed derived metrics) so successive PRs can diff the
perf trajectory with a plain ``git diff`` / ``jq``:
  bench_loading      — paper Table 4  (bulk load times)
  bench_queries      — paper Table 5 / Figs 4,5,7 (MAPSIN vs reduce-side)
  bench_multiway     — paper Fig 6 / §4.3 (star-join single-GET optimization)
  bench_selectivity  — paper §5 analysis (win grows with selectivity) +
                       the planner's cost-based vs heuristic ordering gate
  bench_kernels      — kernel hot-spot microbenches
  bench_serving      — serving layer (DESIGN.md §5): batched engine
                       throughput/latency vs the sequential loop

``python -m benchmarks.run --smoke`` (or ``python -m benchmarks.smoke``)
runs every suite at minimal scale as a crash canary; see smoke.py.

Roofline terms come from the dry-run artifacts: see
``python -m repro.launch.roofline`` (reads experiments/dryrun/*.json).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def run_meta() -> dict:
    """Provenance stamped into every BENCH_*.json: which commit, which
    devices, when.  Each probe degrades to None rather than failing the
    bench (detached checkouts, no-git tarballs, driverless CI)."""
    meta = {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "git_sha": None, "platform": None, "device_count": None}
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if out.returncode == 0:
            meta["git_sha"] = out.stdout.strip()
    except OSError:
        pass
    try:
        import jax
        meta["platform"] = jax.default_backend()
        meta["device_count"] = jax.device_count()
    except Exception:   # noqa: BLE001 — meta must never sink a bench run
        pass
    return meta


def _parse_derived(derived: str) -> dict:
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v) if "." in v or "e" in v.lower() else int(v)
        except ValueError:
            out[k] = v
    return out


def write_bench_json(suite: str, rows: dict, out_dir: str | None = None,
                     meta: dict | None = None) -> str:
    path = os.path.join(out_dir or os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_{suite}.json")
    doc = {"suite": suite, "rows": rows}
    if meta is not None:
        doc["meta"] = meta
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def run_suite(name: str, mod, emit=print, meta: dict | None = None) -> str:
    """Run one suite, tee its CSV lines to `emit`, write BENCH_<name>.json."""
    rows: dict = {}

    def tee(line: str):
        emit(line)
        parts = str(line).split(",", 2)
        if len(parts) >= 2:
            try:
                us = float(parts[1])
            except ValueError:
                return
            rows[parts[0]] = {
                "us": us,
                "derived": _parse_derived(parts[2]) if len(parts) > 2 else {},
            }

    mod.main(emit=tee)
    return write_bench_json(name, rows, meta=meta if meta is not None
                            else run_meta())


def main() -> None:
    args = [a for a in sys.argv[1:]]
    from repro.common import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in args:
        from benchmarks import smoke
        raise SystemExit(smoke.main())
    from benchmarks import (bench_distributed, bench_kernels, bench_loading,
                            bench_multiway, bench_queries, bench_selectivity,
                            bench_serving)
    mods = {
        "loading": bench_loading,
        "queries": bench_queries,
        "multiway": bench_multiway,
        "selectivity": bench_selectivity,
        "kernels": bench_kernels,
        "serving": bench_serving,
        "distributed": bench_distributed,
    }
    only = args[0] if args else None
    print("name,us_per_call,derived")
    meta = run_meta()   # one stamp for the whole invocation
    for name, mod in mods.items():
        if only and name != only:
            continue
        run_suite(name, mod, meta=meta)


if __name__ == "__main__":
    main()
