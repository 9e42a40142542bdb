"""Serving throughput: batched ServeEngine vs the sequential loop.

The paper's workload IS query serving; this harness measures the layer
PR 3 adds on top of the probe engine. An open-loop Poisson stream of
mixed LUBM + SP²Bench queries (each a template with randomized
constants — the many-tenant shape a production front door sees) runs
through two tenants' ServeEngines (shape-bucketing batcher) and through
the sequential one-query-at-a-time `execute_local` loop, on a virtual
clock driven by measured wall times:

  saturated — all requests queued, drained at max_batch: the raw
              queries/sec capacity comparison (the >= 3x acceptance
              gate, recorded as `speedup`), avg batch >= 8;
  poisson   — arrivals at 1.5x the sequential engine's measured
              capacity: p50/p99 latency at a load the sequential loop
              cannot sustain (its queue grows all run) while the
              batcher absorbs it with moderate batches;
  coldstart — first-contact cost: the sequential loop compiles one
              cascade PER DISTINCT QUERY (constants are baked into the
              plan), the engine one per (template, batch-shape). The
              persistent compile cache is off for this phase, so the
              row times compiles whatever the cache holds.

A fourth phase measures the PRODUCTION shape (PR 4): `sharded` runs the
same kind of mixed stream through a ServeEngine bound to a forced
8-device mesh over a region-sharded store with `routing="a2a"` — one
`shard_map` dispatch (one all_to_all pair per cascade step) serves the
whole batch — against the per-query `execute_sharded` loop, recording
qps, avg batch, and the static a2a collective payload per query vs the
single-query tuned routed path (the acceptance gates: >= 3x qps at avg
batch >= 8, payload per query within 1.5x). Runs in a subprocess with
`--xla_force_host_platform_device_count` so the caller's device view is
untouched (same pattern as bench_distributed).

Every batched result is verified bit-identical (row set) to
`execute_local` on the same (patterns, cfg); each distinct template
shape is additionally verified against `execute_oracle` on a small
instance (the oracle is O(N) python per binding — too slow at bench
scale). Stream shapes are the selective serving-style queries; the
broad class scans (LUBM Q6/Q14, SP²B Q2) are batch-analytics, not
request traffic.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

import jax

from repro.common import compile_cache_off
from repro.core import (Caps, ExecConfig, build_store, execute_local,
                        execute_oracle, rows_set)
from repro.core.bgp import order_patterns
from repro.data import lubm_like, sp2b_like
from repro.obs import MetricsRegistry, Tracer
from repro.obs.trace import load_chrome
from repro.serve import EngineBusy, Fault, FaultPlan, ServeEngine

CAPS = Caps(out_cap=128, probe_cap=32, row_cap=16)

# trace/metrics artifacts land here (gitignored); CI uploads the dir
ARTIFACT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts")

# comparative phases verify row-identity against execute_local at the SAME
# caps, which requires identical truncation semantics — so the benchmarked
# engines pin max_escalations=0 (the recovery machinery is measured by the
# fault row below and tested in tests/test_robustness.py)
NO_ESC = dict(max_escalations=0)

N_DEPT, N_PROF, N_COURSE = 12, 18, 24     # rdf_gen.lubm_like constants


def _lubm_shapes(d, n_univ, rng):
    """(name, weight, sampler) — samplers draw random constants."""
    p = d.pattern
    u = lambda: rng.randint(n_univ)
    return [
        ("lubm_q1", 3, lambda: (lambda uu, dd: [
            p("?x", "rdf:type", "GraduateStudent"),
            p("?x", "takesCourse",
              f"Course{rng.randint(N_COURSE)}.D{dd}.U{uu}")])(
                  u(), rng.randint(N_DEPT))),
        ("lubm_q3", 3, lambda: (lambda uu, dd: [
            p("?x", "rdf:type", "Publication"),
            p("?x", "publicationAuthor",
              f"Prof{rng.randint(N_PROF)}.D{dd}.U{uu}")])(
                  u(), rng.randint(N_DEPT))),
        ("lubm_q5", 3, lambda: [
            p("?x", "rdf:type", "Student"),
            p("?x", "memberOf", f"Dept{rng.randint(N_DEPT)}.U{u()}")]),
        ("lubm_q13", 3, lambda: [
            p("?p", "worksFor", f"Dept{rng.randint(N_DEPT)}.U{u()}"),
            p("?x", "advisor", "?p")]),
        ("lubm_q7", 2, lambda: (lambda uu, dd: [
            p("?y", "rdf:type", "Course"),
            p(f"Prof{rng.randint(N_PROF)}.D{dd}.U{uu}", "teacherOf", "?y"),
            p("?x", "takesCourse", "?y"),
            p("?x", "rdf:type", "Student")])(u(), rng.randint(N_DEPT))),
        ("lubm_q11", 1, lambda: [
            p("?x", "rdf:type", "ResearchGroup"),
            p("?x", "subOrganizationOf", f"Univ{u()}")]),
        ("lubm_q4star", 2, lambda: (lambda uu, dd: [
            p("?x", "rdf:type", "Professor"),
            p("?x", "worksFor", f"Dept{dd}.U{uu}"),
            p("?x", "name", "?y1"),
            p("?x", "emailAddress", "?y2"),
            p("?x", "telephone", "?y3")])(u(), rng.randint(N_DEPT))),
    ]


def _sp2b_shapes(d, n_articles, rng):
    p = d.pattern
    n_persons = max(n_articles // 3, 8)
    return [
        ("sp2b_title", 3, lambda: [
            p("?a", "rdf:type", "Article"),
            p("?a", "dc:title", f"title{2 * rng.randint(n_articles // 2)}"),
            p("?a", "dcterms:issued", "?yr")]),
        ("sp2b_author", 3, lambda: [
            p("?a", "dc:creator", f"Person{rng.randint(n_persons)}"),
            p("?a", "dc:title", "?t")]),
        ("sp2b_person", 3, lambda: [
            p("?s", "?pr", f"Person{rng.randint(n_persons)}")]),
    ]


def _gen_stream(tenants, n_requests, rng):
    """Mixed request stream: (tenant, shape name, patterns) per request."""
    choices = [(t, name, fn) for t, shapes in tenants.items()
               for name, w, fn in shapes for _ in range(w)]
    return [(lambda t, name, fn: (t, name, fn()))(*choices[rng.randint(
        len(choices))]) for _ in range(n_requests)]


def _block(bnd):
    jax.block_until_ready((bnd.table, bnd.valid, bnd.overflow))
    return bnd


def _run_sequential(stores, reqs, arrivals):
    """FIFO one-at-a-time loop on a virtual clock; returns (lat, makespan)."""
    now, lat = 0.0, []
    for (tenant, _, pats), arr in zip(reqs, arrivals):
        start = max(now, arr)
        t0 = time.perf_counter()
        _block(execute_local(stores[tenant], pats, "mapsin", caps=CAPS))
        now = start + (time.perf_counter() - t0)
        lat.append(now - arr)
    return lat, now


def _run_batched(engines, reqs, arrivals, max_queue_shed=False):
    """Open-loop replay through the shape-bucketing engines; returns
    (lat, makespan, shed). The engine with the deepest queue steps.
    Submits carry the tenant and steps carry the virtual clock, so the
    engines' per-tenant latency histograms (obs metrics) see the same
    clock domain the replay measures latency on."""
    now, i, shed = 0.0, 0, 0
    lat = []
    arr_of = {}
    n = len(reqs)
    while len(lat) + shed < n:
        while i < n and arrivals[i] <= now:
            tenant, _, pats = reqs[i]
            try:
                rid = engines[tenant].submit(pats, arrival=arrivals[i],
                                             tenant=tenant)
                arr_of[(tenant, rid)] = arrivals[i]
            except EngineBusy:         # admission control: load shed (503)
                if not max_queue_shed:
                    raise
                shed += 1
            i += 1
        busiest = max(engines, key=lambda t: engines[t].pending())
        if engines[busiest].pending() == 0:
            if i < n:
                now = max(now, arrivals[i])
                continue
            break
        t0 = time.perf_counter()
        results = engines[busiest].step(now=now)
        now += time.perf_counter() - t0
        for r in results:
            lat.append(now - arr_of[(busiest, r.request_id)])
    return lat, now, shed


# ---------------------------------------------------------------------------
# Sharded batched serving (forced-multi-device; the production shape)
# ---------------------------------------------------------------------------

SHARDED_SHARDS = 8
SHARDED_SHAPES = ("lubm_q1", "lubm_q3", "lubm_q5", "lubm_q13", "lubm_q4star")


def _seq_payload_bytes(store, pats, cfg, caps, num_shards):
    """Static per-shard a2a collective payload of ONE execute_sharded call
    (embedded measured caps; same convention as ServeEngine._payload_bytes
    and bench_distributed: the local diagonal block is excluded)."""
    from repro.core import compile_plan
    from repro.core.bgp import a2a_step_payload_bytes
    plan = compile_plan(store, pats, caps, routing=cfg.routing,
                        num_shards=num_shards)
    total = 0
    for st in plan.steps[1:]:
        if st.kind not in ("mapsin", "multiway"):
            continue
        cap = (st.caps.row_cap if st.kind == "multiway"
               else st.caps.probe_cap)
        total += a2a_step_payload_bytes(st.caps.a2a_bucket_cap, cap,
                                        num_shards)
    return total


def _sharded_mesh_main(emit=print, num_shards=SHARDED_SHARDS, lubm_scale=2,
                       n_requests=160, max_batch=16, n_variants=3,
                       shape_names=SHARDED_SHAPES, seed=0):
    """Body that runs INSIDE the forced-multi-device process: batched
    sharded engine vs the per-query execute_sharded loop, warm on both
    sides, every batched result verified row-identical to execute_local."""
    from jax.sharding import Mesh

    assert jax.device_count() >= num_shards, jax.devices()
    mesh = Mesh(np.array(jax.devices()[:num_shards]), ("data",))
    cfg = ExecConfig(routing="a2a")
    tr, d, _ = lubm_like(lubm_scale)
    store = build_store(tr, num_shards=num_shards)
    rng = np.random.RandomState(seed)
    shapes = [s for s in _lubm_shapes(d, lubm_scale, rng)
              if s[0] in shape_names]
    # fixed per-template variant pools: the sequential loop compiles (and
    # tunes) per DISTINCT query, so unbounded constants would time compiles
    pools = {name: [fn() for _ in range(n_variants)] for name, _, fn in shapes}
    names = [name for name, _, _ in shapes]
    reqs = [pools[names[rng.randint(len(names))]][rng.randint(n_variants)]
            for _ in range(n_requests)]

    engine = ServeEngine(store, d, cfg, caps=CAPS, mesh=mesh,
                         max_batch=max_batch, max_queue=4 * n_requests,
                         compile_cache_size=64, **NO_ESC)

    def run_seq():
        for pats in reqs:
            from repro.core import execute_sharded
            t, v, ovf, _ = execute_sharded(store, pats, mesh, "mapsin", cfg,
                                           caps=CAPS)
            jax.block_until_ready((t, v, ovf))

    # --- warm-up + verification (compiles and tuning paid here) ----------
    results = engine.execute(reqs)
    run_seq()
    verified, ovf_total, local_cache = 0, 0, {}
    for pats, res in zip(reqs, results):
        key = tuple(pats)
        if key not in local_cache:
            bnd = execute_local(store, pats, "mapsin", cfg, caps=CAPS)
            local_cache[key] = (rows_set(bnd.table, bnd.valid, len(bnd.vars)),
                                tuple(bnd.vars))
        want, vars_ = local_cache[key]
        assert res.rows_set(vars_) == want, pats
        verified += 1
        ovf_total += res.overflow

    # --- timed: batched-sharded vs per-query execute_sharded loop --------
    d0, q0 = engine.dispatches, engine.dispatched_queries
    p0 = engine.a2a_payload_bytes
    t0 = time.perf_counter()
    engine.execute(reqs)
    sat_b = time.perf_counter() - t0
    dispatches = engine.dispatches - d0
    avg_batch = (engine.dispatched_queries - q0) / max(dispatches, 1)
    bytes_q_batched = (engine.a2a_payload_bytes - p0) / n_requests
    t0 = time.perf_counter()
    run_seq()
    sat_s = time.perf_counter() - t0
    qps_b, qps_s = n_requests / sat_b, n_requests / sat_s
    bytes_q_seq = float(np.mean([_seq_payload_bytes(store, pats, cfg, CAPS,
                                                    num_shards)
                                 for pats in reqs]))

    emit(f"bench_serving/sharded{num_shards}_lubm{lubm_scale},"
         f"{sat_b / n_requests * 1e6:.0f},"
         f"qps_batched={qps_b:.1f};qps_seq={qps_s:.1f};"
         f"speedup={qps_b / qps_s:.2f};avg_batch={avg_batch:.1f};"
         f"dispatches={dispatches};"
         f"probe_payload_q_batched={bytes_q_batched:.0f};"
         f"probe_payload_q_seq={bytes_q_seq:.0f};"
         f"bytes_ratio={bytes_q_batched / max(bytes_q_seq, 1e-9):.2f};"
         f"verified_local={verified};distinct={len(local_cache)};"
         f"ovf={ovf_total};n={n_requests}")

    # --- 1%-fault row: serving under injected shard faults (PR 6) --------
    # a seeded Bernoulli(1%) FaultPlan over the answer legs, answer-leg
    # checksums + dispatch retries on; p99 must stay within 2x the clean
    # engine's (measured on the same replay protocol), rows stay exact
    def _replay(eng):
        lat, now = [], 0.0
        for pats in reqs:
            eng.submit(pats, arrival=0.0)
        while eng.pending():
            t0 = time.perf_counter()
            results = eng.step(force=True)
            now += time.perf_counter() - t0
            lat.extend(now for _ in results)
        return lat, now

    # deterministic resample until the plan carries a step-0 fault: tiny
    # meshes can roll an empty 1% plan (2 shards x 2 steps x 32 epochs =
    # 128 trials), and a fault-free row would measure nothing
    fseed = seed + 17
    while True:
        fp = FaultPlan.sample(fseed, num_shards, n_steps=2, rate=0.01,
                              horizon=32)
        step0_epochs = [f.epoch for f in fp.faults if f.step == 0]
        if step0_epochs:
            break
        fseed += 1
    feng = ServeEngine(store, d, cfg, caps=CAPS, mesh=mesh,
                       max_batch=max_batch, max_queue=4 * n_requests,
                       compile_cache_size=64, fault_plan=fp,
                       fault_retries=4, **NO_ESC)
    fresults = feng.execute(reqs)                      # warm + verify
    fverified = funrec = 0
    for pats, res in zip(reqs, fresults):
        want, vars_ = local_cache[tuple(pats)]
        if (res.stats or {}).get("fault_unrecovered"):
            funrec += 1                                # quarantined subset
            assert res.rows_set(vars_) <= want, pats   # never WRONG rows
        else:
            assert res.rows_set(vars_) == want, pats
        fverified += 1
    # pin the measurement to one epoch window — anchored at the first
    # step-0 fault so the window provably exercises >= 1 fault — and warm
    # it first: an untimed replay from W compiles every fault selection
    # the window contains, then rewinding to W makes the timed replay
    # traverse the identical (deterministic) epoch sequence: steady-state
    # dispatch + detect/retry cost, not first-encounter XLA compiles
    window_start = min(step0_epochs)
    feng.fault_epoch = window_start
    _replay(feng)
    feng.fault_epoch = window_start
    detected0, redisp0 = feng.corrupt_detected, feng.fault_redispatches
    lat_f, span_f = _replay(feng)
    win_detected = feng.corrupt_detected - detected0
    win_redisp = feng.fault_redispatches - redisp0
    assert win_detected > 0, \
        "1%-fault window exercised no faults — row would be vacuous"
    lat_c, span_c = _replay(engine)
    p99 = lambda xs: float(np.percentile(np.asarray(xs) * 1e3, 99))
    p99_f, p99_c = p99(lat_f), p99(lat_c)
    emit(f"bench_serving/fault1pct_sharded{num_shards}_lubm{lubm_scale},"
         f"{span_f / n_requests * 1e6:.0f},"
         f"qps_fault={n_requests / span_f:.1f};"
         f"qps_clean={n_requests / span_c:.1f};"
         f"p99_ms_fault={p99_f:.2f};p99_ms_clean={p99_c:.2f};"
         f"p99_fault_ratio={p99_f / max(p99_c, 1e-9):.2f};"
         f"detected={win_detected};"
         f"redispatches={win_redisp};"
         f"unrecovered={funrec};verified_local={fverified};n={n_requests}")


def _chaos_mesh_main(emit=print, num_shards=2, lubm_scale=1, seed=0,
                     trace_path=None):
    """Fast-tier chaos canary (runs INSIDE the forced-device process): a
    seeded FaultPlan with one DROPPED and one CORRUPTED a2a answer leg on
    a 2-device mesh; asserts the checksums detect both, the dispatch loop
    recovers by retrying onto clean epochs, and every delivered row set
    is identical to execute_local — zero wrong rows under chaos.  With
    trace_path set, exports the fault-retry span tree (detect -> retry ->
    clean epoch) as a Perfetto-loadable chrome trace."""
    from jax.sharding import Mesh

    assert jax.device_count() >= num_shards, jax.devices()
    mesh = Mesh(np.array(jax.devices()[:num_shards]), ("data",))
    cfg = ExecConfig(routing="a2a")
    tr, d, _ = lubm_like(lubm_scale)
    store = build_store(tr, num_shards=num_shards)
    rng = np.random.RandomState(seed)
    shapes = [s for s in _lubm_shapes(d, lubm_scale, rng)
              if s[0] in ("lubm_q1", "lubm_q5", "lubm_q13")]
    reqs = [fn() for _, _, fn in shapes for _ in range(2)]
    fp = FaultPlan((Fault(0, 0, "drop", epoch=0),
                    Fault(0, 1, "corrupt", epoch=1)))
    tracer = Tracer() if trace_path else None
    eng = ServeEngine(store, d, cfg, caps=CAPS, mesh=mesh, max_batch=4,
                      fault_plan=fp, tracer=tracer,
                      metrics=MetricsRegistry() if trace_path else None,
                      **NO_ESC)
    t0 = time.perf_counter()
    results = eng.execute(reqs)
    span = time.perf_counter() - t0
    if tracer is not None:
        disp = [s for s in tracer.spans if s.name == "dispatch"]
        assert any(s.attrs.get("bad", 0) > 0 for s in disp), "no fault span"
        assert disp[-1].attrs.get("bad") == 0, "last dispatch not clean"
        os.makedirs(os.path.dirname(trace_path) or ".", exist_ok=True)
        tracer.export(trace_path)
        load_chrome(trace_path)        # Perfetto-loadable or die
    verified = 0
    for pats, res in zip(reqs, results):
        bnd = execute_local(store, pats, "mapsin", cfg, caps=CAPS)
        want = rows_set(bnd.table, bnd.valid, len(bnd.vars))
        assert res.rows_set(tuple(bnd.vars)) == want, pats
        assert "fault_unrecovered" not in (res.stats or {}), pats
        verified += 1
    assert eng.corrupt_detected >= 2, eng.corrupt_detected  # drop + corrupt
    assert eng.fault_redispatches >= 2, eng.fault_redispatches
    emit(f"bench_serving/chaos{num_shards}_lubm{lubm_scale},"
         f"{span / len(reqs) * 1e6:.0f},"
         f"detected={eng.corrupt_detected};"
         f"redispatches={eng.fault_redispatches};"
         f"verified_local={verified};n={len(reqs)}")


def _respawn_forced(spec: dict, num_shards: int, emit):
    """Re-run this module in a subprocess with forced host devices (the
    device-count flag must never leak into the caller's jax), re-emitting
    the child's bench rows. Forced host devices stand in for a mesh only
    on a CPU parent: on an accelerator with too few devices the phase is
    skipped and emits no row — a CPU child would report CPU numbers under
    the accelerator's run (and the parent already holds the chip)."""
    backend = jax.default_backend()
    if backend != "cpu":
        phase = "chaos" if spec.get("chaos") else "sharded"
        print(f"bench_serving: skipped the {phase} phase: it needs "
              f"{num_shards} devices and this {backend} host has "
              f"{jax.device_count()}", file=sys.stderr)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count"
                        f"={num_shards}").strip()
    env["JAX_PLATFORMS"] = "cpu"   # the flag only forces the HOST platform
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serving", json.dumps(spec)],
        env=env, capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    if out.returncode != 0:
        raise RuntimeError(f"bench_serving sharded subprocess failed:\n"
                           f"{out.stderr[-4000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("bench_serving/"):
            emit(line)


def sharded_main(emit=print, num_shards=SHARDED_SHARDS, lubm_scale=2,
                 n_requests=160, max_batch=16, n_variants=3,
                 shape_names=SHARDED_SHAPES, seed=0):
    """Run the sharded serving suite, respawning in a subprocess with
    forced host devices when the current process doesn't have enough
    (the device-count flag must never leak into the caller's jax)."""
    if jax.device_count() >= num_shards:
        return _sharded_mesh_main(emit, num_shards, lubm_scale, n_requests,
                                  max_batch, n_variants, shape_names, seed)
    _respawn_forced({"num_shards": num_shards, "lubm_scale": lubm_scale,
                     "n_requests": n_requests, "max_batch": max_batch,
                     "n_variants": n_variants,
                     "shape_names": list(shape_names), "seed": seed},
                    num_shards, emit)


def chaos_main(emit=print, num_shards=2, lubm_scale=1, seed=0,
               trace_path=None):
    """Run the chaos canary (CI fast tier: benchmarks/smoke.py), forcing
    a 2-device mesh via subprocess when needed."""
    if jax.device_count() >= num_shards:
        return _chaos_mesh_main(emit, num_shards, lubm_scale, seed,
                                trace_path)
    _respawn_forced({"chaos": True, "num_shards": num_shards,
                     "lubm_scale": lubm_scale, "seed": seed,
                     "trace_path": trace_path},
                    num_shards, emit)


def main(emit=print, lubm_scale=2, sp2b_scale=1000, n_requests=192,
         max_batch=16, seed=0, oracle=True, sharded=True):
    rng = np.random.RandomState(seed)
    lt, ld, _ = lubm_like(lubm_scale)
    st, sd, _ = sp2b_like(sp2b_scale)
    stores = {"lubm": build_store(lt, 1), "sp2b": build_store(st, 1)}
    dicts = {"lubm": ld, "sp2b": sd}
    triples = {"lubm": lt, "sp2b": st}
    shapes = {"lubm": _lubm_shapes(ld, lubm_scale, rng),
              "sp2b": _sp2b_shapes(sd, sp2b_scale, rng)}
    reqs = _gen_stream(shapes, n_requests, rng)
    tag = f"lubm{lubm_scale}_sp2b{sp2b_scale}"

    def fresh_engines():
        # compile cache must hold every (template, pow2-batch) pair or the
        # timed phases would re-pay compiles on eviction
        return {t: ServeEngine(stores[t], dicts[t], caps=CAPS,
                               max_batch=max_batch,
                               max_queue=4 * n_requests,
                               compile_cache_size=64, name=t, **NO_ESC)
                for t in stores}

    # --- cold start (compiles included), then warm both paths -------------
    # the persistent compile cache is kept out: these rows time compiles
    engines = fresh_engines()
    zero = [0.0] * n_requests
    with compile_cache_off():
        t0 = time.perf_counter()
        _run_batched(engines, reqs, zero)
        cold_batched = time.perf_counter() - t0
        t0 = time.perf_counter()
        _run_sequential(stores, reqs, zero)
        cold_seq = time.perf_counter() - t0
    # deterministic warm-up: every template at every pow2 batch shape, so
    # neither timed phase below ever waits on a compile (a deployment
    # would do this from a traffic log at startup — ServeEngine.precompile)
    for tenant, _, pats in reqs:
        engines[tenant].precompile(pats)

    # --- saturated throughput (the >= 3x acceptance gate) -----------------
    # wall clock around BOTH loops, so python-side scheduling overhead is
    # charged to the engine that incurs it
    d0 = engines["lubm"].dispatches + engines["sp2b"].dispatches
    t0 = time.perf_counter()
    _run_batched(engines, reqs, zero)
    sat_batched = time.perf_counter() - t0
    dispatches = engines["lubm"].dispatches + engines["sp2b"].dispatches - d0
    t0 = time.perf_counter()
    _run_sequential(stores, reqs, zero)
    sat_seq = time.perf_counter() - t0
    qps_b, qps_s = n_requests / sat_batched, n_requests / sat_seq
    avg_batch = n_requests / max(dispatches, 1)

    # --- observability overhead + coverage gate (ISSUE 8) -----------------
    # re-run the saturated replay on the same warmed engines with a Tracer
    # and a private MetricsRegistry attached, interleaved with untraced
    # re-runs; the qps ratio is the tracing tax (<= 2% at full scale) and
    # the span coverage proves the trace accounts for the engine's wall
    # time. Interleaved min-of-pairs on BOTH sides is the drift-robust
    # estimator on a noisy shared host (machine noise is one-sided — it
    # only ever adds time — so the per-side min approaches each clean
    # time); a genuinely slow tracer cannot hide from it. The tracer is
    # rebuilt per traced run so span accumulation never biases later
    # iterations; the last run's trace is the exported artifact.
    reg = MetricsRegistry()
    prev_reg = {t: engines[t].metrics_registry for t in engines}
    traced_s, off_s = [], []
    tracer = None
    w0 = w1 = 0.0
    for _ in range(8):
        tracer = Tracer()
        for t in engines:
            engines[t].tracer, engines[t].metrics_registry = tracer, reg
        w0 = tracer.now()
        t0 = time.perf_counter()
        _run_batched(engines, reqs, zero)
        traced_s.append(time.perf_counter() - t0)
        w1 = tracer.now()
        for t in engines:
            engines[t].tracer = None
            engines[t].metrics_registry = prev_reg[t]
        t0 = time.perf_counter()
        _run_batched(engines, reqs, zero)
        off_s.append(time.perf_counter() - t0)
        if len(traced_s) >= 3 and min(off_s) / min(traced_s) >= 0.985:
            break
    overhead_ratio = min(off_s) / min(traced_s)   # qps_traced / qps_off
    coverage = tracer.coverage(w0, w1, track="engine")
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    trace_path = os.path.join(ARTIFACT_DIR, "TRACE_serving.json")
    tracer.export(trace_path)
    events = load_chrome(trace_path)   # self-check: Perfetto-loadable
    for t in engines:                  # refresh the qps gauge per engine
        engines[t].metrics_registry = reg
        engines[t].metrics()
        engines[t].metrics_registry = prev_reg[t]
    snap = reg.to_dict()
    with open(os.path.join(ARTIFACT_DIR, "METRICS_serving.json"), "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
        f.write("\n")
    hkeys = snap["histograms"]
    assert any(k.startswith("serve_template_latency_seconds") for k in hkeys)
    assert any(k.startswith("serve_tenant_latency_seconds") for k in hkeys)
    p99_ms = {t: 1e3 * snap["histograms"]
              [f'serve_tenant_latency_seconds{{tenant="{t}"}}']["p99"]
              for t in engines}
    full_scale = n_requests >= 64
    if full_scale:        # smoke runs are too short/noisy to gate on
        assert overhead_ratio >= 0.98, (
            f"tracing costs more than 2% qps: ratio={overhead_ratio:.3f} "
            f"(traced {min(traced_s):.3f}s vs off {min(off_s):.3f}s)")
        assert coverage >= 0.95, (
            f"trace covers only {coverage:.1%} of engine wall time")
    emit(f"bench_serving/traced_{tag},"
         f"{min(traced_s) / n_requests * 1e6:.0f},"
         f"trace_overhead_ratio={overhead_ratio:.3f};"
         f"span_coverage={coverage:.3f};"
         f"qps_traced={n_requests / min(traced_s):.0f};"
         f"trace_events={len(events)};"
         f"p99_ms_lubm={p99_ms['lubm']:.2f};p99_ms_sp2b={p99_ms['sp2b']:.2f}")

    # --- verification: every request vs execute_local; shapes vs oracle ---
    engines_v = fresh_engines()
    rid_to_req = {}
    for (tenant, name, pats), _ in zip(reqs, zero):
        rid = engines_v[tenant].submit(pats)
        rid_to_req[(tenant, rid)] = (tenant, name, pats)
    results = {t: {} for t in engines_v}
    for t, eng in engines_v.items():
        for r in eng.drain():
            results[t][r.request_id] = r
    verified = 0
    local_cache = {}
    for (tenant, rid), (t, name, pats) in rid_to_req.items():
        key = (tenant, tuple(pats))
        if key not in local_cache:
            bnd = execute_local(stores[tenant], pats, "mapsin", caps=CAPS)
            local_cache[key] = (rows_set(bnd.table, bnd.valid, len(bnd.vars)),
                                tuple(bnd.vars))
        want, vars_ = local_cache[key]
        got = results[tenant][rid]
        assert got.rows_set(vars_) == want, (tenant, name, pats)
        verified += 1
    verified_oracle = 0
    if oracle:
        vs = {"lubm": lubm_like(1), "sp2b": sp2b_like(300)}
        orng = np.random.RandomState(seed + 1)
        vshapes = {t: _lubm_shapes(vs[t][1], 1, orng) if t == "lubm"
                   else _sp2b_shapes(vs[t][1], 300, orng) for t in vs}
        for t, shp in vshapes.items():
            tr_v, d_v, _ = vs[t]
            store_v = build_store(tr_v, 1)
            eng_v = ServeEngine(store_v, d_v, caps=CAPS,
                                max_batch=max_batch, **NO_ESC)
            for name, _, fn in shp:
                pats = fn()
                res = eng_v.execute([pats])[0]
                # ordered patterns: same result set, tractable oracle
                want, ovars = execute_oracle(
                    tr_v, order_patterns(pats, store=store_v))
                assert res.rows_set(ovars) == want, (t, name)
                verified_oracle += 1

    emit(f"bench_serving/saturated_{tag},{sat_batched / n_requests * 1e6:.0f},"
         f"qps_batched={qps_b:.0f};qps_seq={qps_s:.0f};"
         f"speedup={qps_b / qps_s:.2f};avg_batch={avg_batch:.1f};"
         f"dispatches={dispatches};n={n_requests};"
         f"verified_local={verified};verified_oracle={verified_oracle}")

    # --- open-loop Poisson at 1.5x the sequential engine's capacity -------
    # a load the one-at-a-time loop cannot sustain (its queue grows for
    # the whole run) while the batcher absorbs it with moderate batches;
    # note an open-loop batcher's capacity is batch-size dependent, so
    # rates near qps_batched (which assumes full batches) also saturate
    rate = 1.5 * qps_s
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests)).tolist()
    # untimed replay first: an arrival trickle dispatches small batch
    # shapes (1/2/4/...) the saturated phase never compiled; the timed
    # replay below then measures steady-state latency, not compiles
    _run_batched(engines, reqs, arrivals, max_queue_shed=True)
    lat_b, _, shed = _run_batched(engines, reqs, arrivals,
                                  max_queue_shed=True)
    lat_s, _ = _run_sequential(stores, reqs, arrivals)
    p = lambda xs, q: float(np.percentile(np.asarray(xs) * 1e3, q))
    emit(f"bench_serving/poisson_{tag},{p(lat_b, 99) * 1e3:.0f},"
         f"rate_qps={rate:.0f};p50_ms_batched={p(lat_b, 50):.2f};"
         f"p99_ms_batched={p(lat_b, 99):.2f};p50_ms_seq={p(lat_s, 50):.2f};"
         f"p99_ms_seq={p(lat_s, 99):.2f};shed={shed}")

    emit(f"bench_serving/coldstart_{tag},{cold_batched * 1e6:.0f},"
         f"cold_s_batched={cold_batched:.2f};cold_s_seq={cold_seq:.2f};"
         f"cold_speedup={cold_seq / cold_batched:.2f};"
         f"distinct_queries={len(local_cache)}")

    # --- sharded batched serving (forced 8-device subprocess) -------------
    if sharded:
        sharded_main(emit, seed=seed)
    return qps_b / qps_s


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0].startswith("{"):
        spec = json.loads(args[0])
        if jax.device_count() < spec["num_shards"]:   # spec arg == we ARE
            raise SystemExit(                         # the child; no respawn
                f"forced host devices ineffective: {jax.devices()}")
        if spec.get("chaos"):
            _chaos_mesh_main(print, spec["num_shards"], spec["lubm_scale"],
                             spec["seed"], spec.get("trace_path"))
        else:
            _sharded_mesh_main(print, spec["num_shards"], spec["lubm_scale"],
                               spec["n_requests"], spec["max_batch"],
                               spec["n_variants"],
                               tuple(spec["shape_names"]), spec["seed"])
    else:
        from benchmarks.run import run_suite
        import benchmarks.bench_serving as mod
        run_suite("serving", mod)
