"""One run of one cell: set-up, the measured window, the check of the
answers against the reference, and the result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under ``bench/``, found by the names in
``BENCHMARK.json``:

* ``bench/configs/<config>.json`` (its ``file`` entry) names the data
  generator ``bench/gen/<generator>.py``, its scale and the engine's
  settings;
* ``bench/traffic/<traffic>.json`` is read by ``traffic.Traffic``;
* ``bench/metrics/<metric>.py`` defines ``read(w: Window)`` for each
  metric, end-to-end and per-layer alike, and returns None where it finds
  nothing to read (the metric is then left out of the line).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import loops, tracing
from bench.reference import Reference
from bench.traffic import SAMPLE, Traffic, load, rng_for

# caps of the control: the engine's own bounded-inexact path
# (``inexact_ok=True``, answers cut at the cap and served as they are)
# at a budget that cuts most answers of more than four rows
CONTROL_CAPS = {"probe_cap": 2, "row_cap": 2, "out_cap": 4}
# the numbers compared and their limits: an exact comparison
LIMITS = {"wrong": 0, "missing": 0}


def log(msg: str, err=None) -> None:
    print(msg, file=err or sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the specification
# --------------------------------------------------------------------------


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The cell's metrics: its end-to-end ones with ``--trace 0``, its
    per-layer ones with ``--trace 1``; an entry with a ``workloads`` key
    applies to the cells it lists, one without it to every cell."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in spec[key]
            if workload in m.get("workloads", [workload])]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# what the readers read
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    """The measured window as the metric readers see it."""
    seconds: float
    setup_s: float
    served: loops.Served
    spans: list                 # Tracer spans that began in the window
    counters: dict              # engine counters: change over the window
    device_trace: dict | None   # tracing.reduce of the profiled part

    def delivered_in_window(self) -> int:
        s = self.served
        return sum(1 for d in s.delivered
                   if d is not None and s.t0 <= d <= s.t1)

    def span_mean_ms(self, name: str) -> float | None:
        d = [sp.t1 - sp.t0 for sp in self.spans if sp.name == name]
        return 1e3 * float(np.mean(d)) if d else None


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------


class _Inexact:
    """The control's client: every request opts into the engine's
    bounded-inexact path."""

    def __init__(self, eng):
        self.eng = eng

    def submit(self, text):
        return self.eng.submit(text, inexact_ok=True)

    def step(self):
        return self.eng.step()


def _counters(eng) -> dict:
    reg = eng.metrics_registry
    return {"plan_hits": reg.counter("serve_plan_cache_hits_total").value,
            "plan_misses": reg.counter("serve_plan_cache_misses_total").value,
            "dispatches": eng.dispatches,
            "dispatched_queries": eng.dispatched_queries,
            "escalations": eng.escalations, "fallbacks": eng.fallbacks}


def warm_up(eng, client, traffic: Traffic, clients: int, rounds: int,
            err) -> None:
    """Compile every program the window can use and fill the caches:
    each query is served `rounds` times at every batch size (powers of
    two) that the cell's clients can fill, as that many copies sent
    together. Every escalation rung and exact fallback a query reaches
    compiles here, at that batch size."""
    t = time.perf_counter()
    buckets = []
    b = 1
    while b <= min(eng.max_batch, clients):
        buckets.append(b)
        b <<= 1
    e0, f0 = eng.escalations, eng.fallbacks
    for _ in range(rounds):
        for text in traffic.texts:
            for b in buckets:
                for _ in range(b):
                    client.submit(text)
                while eng.pending():
                    client.step()
    log(f"[setup] warm_up_s={time.perf_counter() - t:.2f} "
        f"(batch sizes {buckets}, {rounds} rounds; escalations="
        f"{eng.escalations - e0} fallbacks={eng.fallbacks - f0})", err)


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------


def check(served: loops.Served, traffic: Traffic, triples, terms,
          n_sample: int, seed: int, err) -> dict:
    """Hold a seeded sample of the window's answers, and its largest one,
    to the reference. Returns the compared numbers: ``wrong`` (sampled
    answers that differ from the reference or are incomplete) and
    ``missing`` (requests of the window that the engine took and never
    answered, shed or timed out)."""
    from repro.serve import QueryShed, QueryTimeout
    n = len(served.submitted)
    answered = [k for k in range(n) if served.delivered[k] is not None
                and not isinstance(served.results[k],
                                   (QueryTimeout, QueryShed))]
    # a request the engine refused (EngineBusy) was answered "busy": it
    # failed, but it is not missing
    missing = n - len(answered) - len(served.refused)
    u = rng_for(seed, SAMPLE).random(n)
    sample = sorted(answered, key=lambda k: u[k])[:n_sample]
    if answered:
        largest = max(answered, key=lambda k: len(served.results[k].rows))
        if largest not in sample:
            sample.append(largest)
    t = time.perf_counter()
    term_id = {term: i for i, term in enumerate(terms)}
    ref = Reference(triples)
    wanted: dict[int, set] = {}          # the reference's answer by query
    wrong = 0
    rows_checked = 0
    for k in sample:
        res = served.results[k]
        req = traffic.request(k)
        pats = traffic.patterns(req.query, term_id)
        vars_ = {v for p in pats for v in p if isinstance(v, str)}
        st = res.stats or {}
        complete = (res.overflow == 0 and not st.get("degraded")
                    and not st.get("fault_unrecovered"))
        ok = complete and set(res.vars) == vars_
        if ok:
            if req.query not in wanted:
                wanted[req.query] = (tuple(res.vars),
                                     ref.rows(pats, res.vars))
            order, want = wanted[req.query]
            perm = [order.index(v) for v in res.vars]
            want = {tuple(row[i] for i in perm) for row in want}
            ok = res.rows_set() == want and len(res.rows) == len(want)
        rows_checked += len(res.rows)
        if not ok:
            wrong += 1
            if wrong <= 5:
                log(f"[check] wrong answer: request {k} "
                    f"{traffic.queries[req.query]['name']}: "
                    f"{len(res.rows)} rows, "
                    f"overflow={res.overflow} complete={complete}", err)
    log(f"[check] {len(sample)} answers checked ({rows_checked} rows, "
        f"largest {len(served.results[largest].rows) if answered else 0}) "
        f"of {n} requests; reference_s={time.perf_counter() - t:.2f}", err)
    return {"wrong": wrong, "missing": missing}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    """A cell set up for its window: the program under test, warmed up,
    and what the check needs."""
    workload: dict
    config: dict
    tspec: dict
    traffic: Traffic
    triples: np.ndarray
    terms: list
    eng: object
    client: object
    device: dict
    devs: list
    watch: object


def prepare(root: str, workload: str, seed: int, device_check, *,
            overrides: dict | None = None,
            traffic_overrides: dict | None = None, control: bool = False,
            trace: bool = False, cache_dir: str | None = None,
            err=None) -> Cell:
    """Check the device, generate the data, build the store and the
    engine, and warm them up: the whole set-up of a run. With `trace` the
    engine is traced from the start, so that its traced paths (the exact
    fallback runs an instrumented one) compile in the warm-up too."""
    spec = load_spec(root)
    wl = find(spec["workloads"], workload, "workload")
    cfg_entry = find(spec["configs"], wl["config"], "config")
    config = load(os.path.join(root, cfg_entry["file"]))
    config.update(overrides or {})
    bench_dir = os.path.join(root, "bench")
    tspec = load(os.path.join(bench_dir, "traffic", wl["traffic"] + ".json"))
    tspec.update(traffic_overrides or {})

    device = device_check(int(wl["chips"]))
    import jax
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench.device import CompileWatch
    watch = CompileWatch()
    devs = jax.devices()[:int(wl["chips"])]
    log(f"[device] {device} jax={jax.__version__}", err)

    from repro.core import build_store
    from repro.core.planner import Caps
    from repro.core.rdf import Dictionary
    from repro.obs.metrics import MetricsRegistry
    from repro.serve import ServeEngine

    t = time.perf_counter()
    gen = load_module(os.path.join(bench_dir, "gen",
                                   config["generator"] + ".py"),
                      "bench_gen_" + config["generator"])
    # the deployment's data is fixed by its configuration (LUBM(50,0)
    # names its generator seed)
    triples, terms = gen.generate(config, int(config["data_seed"]))
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    d = Dictionary()
    for term in terms:
        d.id(term)
    store = build_store(triples)
    jax.block_until_ready((store.keys_spo, store.keys_ops))
    t_build = time.perf_counter() - t
    log(f"[setup] {wl['config']}: triples={len(triples)} terms={len(terms)} "
        f"index_bytes={store.storage_bytes()} generate_s={t_gen:.2f} "
        f"build_s={t_build:.2f}", err)

    kw = dict(config.get("engine", {}))
    if control:
        kw["caps"] = Caps(**CONTROL_CAPS)
    eng = ServeEngine(store, d, metrics=MetricsRegistry(), **kw)
    if trace:
        from repro.obs.trace import Tracer
        eng.tracer = Tracer(jax_profiler=True)
    client = _Inexact(eng) if control else eng
    traffic = Traffic(tspec)
    warm_up(eng, client, traffic, int(tspec["clients"]),
            int(tspec["warmup_rounds"]), err)
    log(f"[setup] compiles: {watch.line()}", err)
    return Cell(wl, config, tspec, traffic, triples, terms, eng, client,
                device, devs, watch)


def per_query(served: loops.Served, traffic: Traffic) -> str:
    """Requests and median latency of each query in the window."""
    lat: dict[str, list] = {}
    for k, (t0, t1) in enumerate(zip(served.submitted, served.delivered)):
        if t1 is not None:
            name = traffic.queries[traffic.request(k).query]["name"]
            lat.setdefault(name, []).append(t1 - t0)
    return " ".join(f"{q}:n={len(v)},p50_ms={1e3 * np.median(v):.1f}"
                    for q, v in lat.items())


def drive(cell: Cell, seconds: float, hooks: loops.Hooks) -> loops.Served:
    from repro.serve import EngineBusy
    traffic = cell.traffic
    return loops.closed_loop(cell.client, lambda k: traffic.request(k).text,
                             int(cell.tspec["clients"]), seconds, hooks,
                             EngineBusy)


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, device_check, *, overrides: dict | None = None,
        traffic_overrides: dict | None = None, control: bool = False,
        cache_dir: str | None = None, out=None, err=None) -> dict:
    """Run one cell once and print its result line; returns the result.

    `device_check(chips)` returns the device description or exits;
    `overrides` and `traffic_overrides` replace keys of the configuration
    and the traffic file (tests run a cell at a tiny scale with them);
    `control` runs the control instead of the program's exact path;
    `cache_dir` is JAX's persistent compilation cache (None: left as it
    is)."""
    out = out or sys.stdout
    spec = load_spec(root)
    wanted = metrics_for(spec, workload, trace)
    bench_dir = os.path.join(root, "bench")
    readers = {m["name"]: load_module(
        os.path.join(bench_dir, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")).read for m in wanted}
    cell = prepare(root, workload, seed, device_check, overrides=overrides,
                   traffic_overrides=traffic_overrides, control=control,
                   trace=trace, cache_dir=cache_dir, err=err)
    import jax
    from bench.device import memory_peak_bytes
    eng, tspec, watch, device = cell.eng, cell.tspec, cell.watch, cell.device

    tracer = eng.tracer if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    hooks = loops.Hooks(annotate=trace)
    before = _counters(eng)
    compiles0 = watch.snapshot()
    gc.collect()
    setup_s = time.perf_counter() - t_start
    if trace:                       # a traced run profiles the whole window
        jax.profiler.start_trace(trace_dir)
    served = drive(cell, seconds, hooks)
    if trace:
        jax.profiler.stop_trace()
    after = _counters(eng)
    counters = {k: after[k] - before[k] for k in after}
    log(f"[window] clients={tspec['clients']} seconds={seconds} "
        f"requests={len(served.submitted)} refused={len(served.refused)} "
        f"drain_s={served.t_end - served.t1:.3f} {counters}", err)
    log(f"[window] inside the window: {watch.line(compiles0)}", err)
    log(f"[window] per query: {per_query(served, cell.traffic)}", err)
    spans = ([sp for sp in tracer.spans if sp.t0 >= served.t0]
             if tracer is not None else [])
    eng.tracer = None
    device["memory_peak_bytes"] = memory_peak_bytes(cell.devs)
    cell.eng = cell.client = eng = None
    del tracer
    gc.collect()

    device_trace = None
    if trace:
        ops, host, inventory = tracing.load_xplane(trace_dir)
        log(f"[trace] {len(ops)} device ops, {len(host)} host spans; "
            f"{inventory}", err)
        device_trace = tracing.reduce(ops, host)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if device_trace is not None:
            device["busy_s"] = device_trace["busy_s"]
            device["window_s"] = device_trace["window_s"]

    numbers = check(served, cell.traffic, cell.triples, cell.terms,
                    int(tspec["check_sample"]), seed, err)
    w = Window(seconds, setup_s, served, spans, counters, device_trace)
    metrics = {}
    for m in wanted:
        v = readers[m["name"]](w)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    correct = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    result = {"correct": correct, "attempted": len(served.submitted),
              "failed": numbers["missing"] + len(served.refused),
              "metrics": metrics,
              "device": device}
    if trace and device_trace is not None:
        result["breakdown"] = {"device_ops": device_trace["device_ops"],
                               "idle_gaps": device_trace["idle_gaps"]}
    result["check"] = {k: {"value": numbers[k], "limit": LIMITS[k]}
                       for k in LIMITS}
    for name, v in metrics.items():
        log(f"[metric] {name}={v['value']} {v['unit']}", err)
    for k in LIMITS:
        log(f"check {k}={numbers[k]} limit={LIMITS[k]}", err)
    print(json.dumps(result), file=out, flush=True)
    return result
