"""Routed vs broadcast dist_probe on a real (forced-host) 8-device mesh.

The paper's network argument says MAPSIN ships ONLY probe keys and ONLY
matching tuples; the point-to-point a2a dispatch (core/distributed.py,
DESIGN.md §2) additionally ships each probe only to the region(s) its
range intersects — O(B) on the key leg instead of the broadcast's O(S·B).
This suite MEASURES that claim instead of modeling it:

  * wall time of ``execute_sharded`` per query under routing="broadcast"
    and routing="a2a" on an 8-shard store over 8 host devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the flag is
    applied in a subprocess so the caller's device view is untouched);
  * probe bytes from the measured probe→region fan-out ("deliveries",
    recorded by the instrumented executor with route_shards == the mesh
    size, so ``query_traffic_actual`` uses the measured branch, not the
    broadcast-equivalent fallback);
  * the static collective payloads both routings actually ship (padded
    buffers — the SPMD emulation's wire format).

Every query is also checked bit-identical between the two routings
(rows_set equality) before its timings are reported — a routing that
drops probes would fail loudly here, not skew the numbers.

Writes ``BENCH_distributed.json`` (via benchmarks.run.run_suite) when run
as ``python -m benchmarks.bench_distributed``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

NUM_SHARDS = 8
LUBM_QUERIES = ("Q1", "Q4", "Q7", "Q14")
SP2B_QUERIES = ("Q3a", "Q10")


def _mesh_main(emit=print, lubm_queries=LUBM_QUERIES,
               sp2b_queries=SP2B_QUERIES, repeats: int = 3):
    """Body that runs INSIDE the 8-device process."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import Caps, ExecConfig, build_store, execute_local
    from repro.core.bgp import (compile_plan, execute_sharded,
                                query_traffic_actual, rows_set)
    from repro.data import lubm_like, sp2b_like

    assert jax.device_count() >= NUM_SHARDS, jax.devices()
    mesh = Mesh(np.array(jax.devices()[:NUM_SHARDS]), ("data",))
    caps = Caps(scan_cap=1 << 14, out_cap=1 << 12, probe_cap=64,
                row_cap=64, bucket_cap=1 << 11)

    def timed(fn):
        jax.block_until_ready(fn())                     # compile
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return min(ts)

    def payload_bytes(plan, routing: str) -> int:
        """Static bytes one shard ships per execution through the probe
        collectives (the padded buffers all_gather/all_to_all move), from
        the plan's OWN embedded per-step caps. One convention for both
        routings: the local block — the all_to_all diagonal / this shard's
        own all_gather contribution / the psum_scatter chunk that stays
        home — never crosses the network and is excluded everywhere."""
        from repro.core.distributed import auto_bucket_cap
        s = NUM_SHARDS
        total = 0
        for st in plan.steps:
            if st.kind == "scan":
                continue
            b = st.caps.out_cap
            cap = (st.caps.row_cap if st.kind == "multiway"
                   else st.caps.probe_cap)
            if routing == "a2a":
                from repro.core.bgp import a2a_step_payload_bytes
                bc = st.caps.a2a_bucket_cap or auto_bucket_cap(b, s)
                total += a2a_step_payload_bytes(bc, cap, s)
            else:
                rec = (s - 1) * b * (8 + 8 + 24)        # all_gather probes
                cnts = (s - 1) * s * b * 4              # all_gather counts
                back = (s - 1) * b * cap * 8            # psum_scatter ring
                total += rec + cnts + back
        return total

    for bench, gen, queries in (("lubm", lubm_like, lubm_queries),
                                ("sp2b", sp2b_like, sp2b_queries)):
        arg = 1 if bench == "lubm" else 2000
        tr, d, qs = gen(arg)
        store = build_store(tr, num_shards=NUM_SHARDS)
        local_store = build_store(tr, num_shards=1)
        for qname in queries:
            pats = qs[qname]
            res, rows, plans = {}, {}, {}
            for routing in ("broadcast", "a2a"):
                rcfg = ExecConfig(routing=routing)
                t, v, ovf, vars_ = execute_sharded(store, pats, mesh,
                                                   "mapsin", rcfg, caps=caps)
                rows[routing] = rows_set(t, v, len(vars_))
                res[routing] = timed(lambda c=rcfg: execute_sharded(
                    store, pats, mesh, "mapsin", c, caps=caps))
                res[routing + "_ovf"] = int(np.asarray(ovf).sum())
                plans[routing] = compile_plan(store, pats, caps,
                                              routing=routing,
                                              num_shards=NUM_SHARDS)
            assert rows["a2a"] == rows["broadcast"], \
                f"{bench}/{qname}: a2a != broadcast ({len(rows['a2a'])} vs " \
                f"{len(rows['broadcast'])} rows)"
            # measured fan-out -> measured routed bytes (route_shards == mesh)
            stats: list = []
            execute_local(local_store, pats, "mapsin", caps=caps,
                          stats=stats, route_shards=NUM_SHARDS)
            routed = query_traffic_actual(stats, "mapsin_routed", NUM_SHARDS,
                                          local_store.n_triples)
            emit(f"bench_distributed/{bench}_{qname},"
                 f"{res['a2a'] * 1e6:.0f},"
                 f"a2a_us={res['a2a'] * 1e6:.0f};"
                 f"broadcast_us={res['broadcast'] * 1e6:.0f};"
                 f"time_ratio={res['broadcast'] / max(res['a2a'], 1e-9):.2f};"
                 f"probe_bytes_routed={routed['probe_bytes_routed']};"
                 f"probe_bytes_broadcast={routed['probe_bytes_broadcast']};"
                 f"net_routed={routed['network']};"
                 f"payload_a2a={payload_bytes(plans['a2a'], 'a2a')};"
                 f"payload_broadcast="
                 f"{payload_bytes(plans['broadcast'], 'broadcast')};"
                 f"rows={len(rows['a2a'])};"
                 f"identical=1;ovf={res['a2a_ovf']}")


def main(emit=print, lubm_queries=LUBM_QUERIES, sp2b_queries=SP2B_QUERIES,
         repeats: int = 3):
    """Relaunch in a subprocess with 8 forced host devices when the current
    process doesn't have them (the device-count flag must never leak into
    the caller's jax); otherwise run in place."""
    import jax
    if jax.device_count() >= NUM_SHARDS:
        return _mesh_main(emit, lubm_queries, sp2b_queries, repeats)
    if jax.default_backend() != "cpu":
        # forced host devices are a CPU stand-in: under an accelerator's
        # run they would report CPU numbers (and the parent holds the chip)
        print(f"bench_distributed: skipped: it needs {NUM_SHARDS} devices "
              f"and this {jax.default_backend()} host has "
              f"{jax.device_count()}", file=sys.stderr)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={NUM_SHARDS}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"   # the flag only forces the HOST platform
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    spec = json.dumps({"lubm": list(lubm_queries), "sp2b": list(sp2b_queries),
                       "repeats": repeats})
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_distributed", spec],
        env=env, capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    if out.returncode != 0:
        raise RuntimeError(f"bench_distributed subprocess failed:\n"
                           f"{out.stderr[-4000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("bench_distributed/"):
            emit(line)


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0].startswith("{"):
        spec = json.loads(args[0])
        import jax
        if jax.device_count() < NUM_SHARDS:      # spec arg == we ARE the
            raise SystemExit(                    # child; never respawn
                f"forced host devices ineffective: {jax.devices()}")
        _mesh_main(print, tuple(spec["lubm"]), tuple(spec["sp2b"]),
                   spec["repeats"])
    else:
        from benchmarks.run import run_suite
        import benchmarks.bench_distributed as mod
        run_suite("distributed", mod)
