"""The control of a cell: the run with the program's bounded-inexact path
switched on (``inexact_ok=True`` at ``harness.CONTROL_CAPS``), which
breaks the configuration's exact-answer guarantee; or, with ``--fault``,
the program's exact path with a fault of ``bench/faults.py`` planted.
The check has to call every such run not correct. Not part of the
benchmark's own runs.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10 \
        [--fault altered_answer|half_batch_dropped]

Prints each run's result line, then one JSON line with the smallest
reading of each compared number over the seeds (the readings the limits
are set below). Needs a TPU.
"""
import time

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench.device import require_tpu
    from bench import loops
    from bench.faults import FAULTS
    from bench.harness import LIMITS, run
    if args.fault is not None:
        FAULTS[args.fault]()
        loops.GRACE_S = 5.0            # a dropped answer never comes
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run(ROOT, args.workload, seed, args.seconds, False,
                  time.perf_counter(), require_tpu,
                  control=args.fault is None,
                  cache_dir=os.path.join(ROOT, ".bench_cache", "jax"))
        readings.append({k: res["check"][k]["value"] for k in LIMITS})
    print(json.dumps({"control": args.workload, "fault": args.fault,
                      "seeds": args.seeds,
                      "least": {k: min(r[k] for r in readings)
                                for k in LIMITS},
                      "any_correct": any(all(r[k] <= LIMITS[k]
                                             for k in LIMITS)
                                         for r in readings)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
