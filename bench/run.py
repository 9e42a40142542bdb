"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cells, configurations and metrics are those of ``BENCHMARK.json`` at
the root of the checkout. The run needs a TPU with as many chips as the
cell asks for, and exits nonzero with no result line without one. It keeps
JAX's persistent compilation cache in ``<checkout>/.bench_cache/jax``, so
only a checkout's first run of a cell compiles. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``check``: each number compared with the reference beside its limit.
"""
import time

T_START = time.perf_counter()            # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench.device import require_tpu
    from bench.harness import run
    run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
        T_START, require_tpu,
        cache_dir=os.path.join(ROOT, ".bench_cache", "jax"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
