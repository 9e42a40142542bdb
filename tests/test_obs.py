"""Observability layer (DESIGN.md §8): query-lifecycle span tracing with
Chrome/Perfetto trace-event export, the process metrics registry
(counters / gauges / fixed-bucket histograms with Prometheus text), and
their wiring through ServeEngine — including the span tree of a
multi-rung escalated query, the detect -> retry -> clean-epoch shape of
a seeded fault run, the metrics-off guarantee (global registry untouched
when disabled), explain()'s estimated-vs-actual drift column, and the
`repro.serve` lifecycle logger (silent at the default WARNING level)."""
import json
import logging

import numpy as np
import pytest

from repro.core import (Caps, ExecConfig, Pattern, build_store,
                        compile_plan, execute_local, explain)
from repro.obs import (DEFAULT_LATENCY_BUCKETS, NULL_REGISTRY, REGISTRY,
                       Histogram, MetricsRegistry, Tracer)
from repro.obs.trace import load_chrome, validate_events
from repro.serve import Fault, FaultPlan, ServeEngine

TINY = Caps(scan_cap=4096, out_cap=8, probe_cap=2, row_cap=4)
CHAIN = [Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z")]


def random_graph(rng, n=500, subjects=40, preds=5, objects=40):
    return np.stack([rng.randint(0, subjects, n),
                     rng.randint(100, 100 + preds, n),
                     rng.randint(0, objects, n)], 1).astype(np.int32)


def _mesh1():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("data",))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_histogram_bucket_boundaries():
    h = Histogram((1.0, 2.0, 4.0))
    # observation equal to a bound lands in that bound's bucket (le=bound)
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 99.0):
        h.observe(v)
    # counts per bucket: le=1 -> {0.5, 1.0}; le=2 -> {1.5, 2.0};
    # le=4 -> {4.0}; +inf -> {99.0}
    assert list(h.counts) == [2, 2, 1, 1]
    assert h.count == 6 and h.sum == pytest.approx(108.0)
    cum = h.cumulative()
    assert cum == [(1.0, 2), (2.0, 4), (4.0, 5), (float("inf"), 6)]
    # +inf terminal bucket is appended automatically and exactly once
    assert h.bounds[-1] == float("inf") and h.bounds[:-1] == (1.0, 2.0, 4.0)


def test_histogram_quantiles_interpolate():
    h = Histogram((10.0, 20.0, 40.0))
    for _ in range(50):
        h.observe(5.0)     # le=10
    for _ in range(50):
        h.observe(15.0)    # le=20
    assert h.quantile(0.5) == pytest.approx(10.0, rel=0.05)
    assert 10.0 < h.quantile(0.9) <= 20.0
    # the +inf bucket has no upper edge: quantiles falling there report
    # the observed max instead of infinity
    h.observe(1e6)
    assert h.quantile(0.999) == pytest.approx(1e6)
    assert h.quantile(0.0) <= h.quantile(1.0)


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram((1.0, 1.0))


def test_registry_instruments_and_labels():
    reg = MetricsRegistry()
    reg.counter("reqs_total", tenant="a").inc()
    reg.counter("reqs_total", tenant="a").inc(2)
    reg.counter("reqs_total", tenant="b").inc()
    reg.gauge("depth").set(7)
    reg.histogram("lat_seconds").observe(0.01)
    d = reg.to_dict()
    assert d["counters"]['reqs_total{tenant="a"}'] == 3
    assert d["counters"]['reqs_total{tenant="b"}'] == 1
    assert d["gauges"]["depth"] == 7
    assert d["histograms"]["lat_seconds"]["count"] == 1
    # one name = one instrument kind, enforced
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")
    # prometheus text exposition: cumulative le= buckets + sum/count
    text = reg.to_prom_text()
    assert 'reqs_total{tenant="a"} 3' in text
    assert 'le="+Inf"' in text and "lat_seconds_count 1" in text


def test_registry_hooks_fire_on_tick():
    reg = MetricsRegistry()
    seen = []
    reg.add_hook(10.0, lambda r: seen.append(r.to_dict()))
    assert reg.tick(now=0.0) == 0      # first tick arms, does not fire
    assert reg.tick(now=5.0) == 0      # interval not yet elapsed
    assert reg.tick(now=11.0) == 1
    assert reg.tick(now=12.0) == 0
    assert reg.tick(now=25.0) == 1
    assert len(seen) == 2 and isinstance(seen[0], dict)


def test_null_registry_is_inert():
    NULL_REGISTRY.counter("x").inc()
    NULL_REGISTRY.gauge("y", a="b").set(3)
    NULL_REGISTRY.histogram("z").observe(1.0)
    assert NULL_REGISTRY.tick() == 0
    assert NULL_REGISTRY.to_dict() == {"counters": {}, "gauges": {},
                                       "histograms": {}}


def test_default_latency_buckets_ascend():
    bs = DEFAULT_LATENCY_BUCKETS
    assert all(a < b for a, b in zip(bs, bs[1:]))
    assert bs[0] <= 1e-4 and bs[-1] == float("inf")


# ---------------------------------------------------------------------------
# tracer + chrome export
# ---------------------------------------------------------------------------


def test_tracer_span_nesting_and_double_end():
    tr = Tracer()
    with tr.span("outer") as o:
        with tr.span("inner"):
            pass
    inner = tr.find("inner")[0]
    assert inner.parent_id == o.span_id and inner.t1 >= inner.t0
    with pytest.raises(ValueError):
        tr.end(o)                      # already ended by the ctx manager
    assert tr.open_count == 0


def test_trace_json_round_trips(tmp_path):
    tr = Tracer()
    root = tr.begin("query", track="query", async_id=7, tenant="t0")
    child = tr.begin("queued", track="query", parent=root, async_id=7)
    tr.end(child)
    tr.end(root, outcome="ok")
    s = tr.begin("step", track="engine")
    tr.end(s, delivered=3)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    events = load_chrome(str(path))    # validates schema on load
    validate_events(events)
    phs = sorted(e["ph"] for e in events)
    assert "X" in phs and "b" in phs and "e" in phs and "M" in phs
    # async b/e events carry the query id so Perfetto nests them per query
    bs = [e for e in events if e["ph"] == "b"]
    assert all(e["id"] == 7 for e in bs)
    # attrs survive the round trip
    x = [e for e in events if e["ph"] == "X"][0]
    assert x["args"]["delivered"] == 3
    raw = json.loads(path.read_text())
    assert set(raw) == {"traceEvents", "displayTimeUnit"}


def test_validate_events_catches_unbalanced_async():
    bad = [{"ph": "b", "pid": 1, "tid": 1, "ts": 0, "cat": "q", "id": 1,
            "name": "x"}]
    with pytest.raises(ValueError, match="unbalanced"):
        validate_events(bad)


def test_coverage_merges_overlaps():
    tr = Tracer(clock=lambda: 0.0)
    tr.record("a", 0.0, 0.6)
    tr.record("b", 0.4, 0.8)           # overlaps a: union is [0, 0.8]
    tr.record("c", 0.9, 1.0)
    assert tr.coverage(0.0, 1.0) == pytest.approx(0.9)
    assert tr.coverage(0.0, 0.5) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# engine wiring: span tree of a multi-rung escalated query
# ---------------------------------------------------------------------------


def test_escalated_query_span_tree(rng):
    store = build_store(random_graph(rng), 1)
    tr = Tracer()
    reg = MetricsRegistry()
    eng = ServeEngine(store, caps=TINY, max_escalations=3, tracer=tr,
                      metrics=reg)
    res = eng.execute([CHAIN])[0]
    assert tr.open_count == 0, [s.name for s in tr.open_spans()]
    by_id = {s.span_id: s for s in tr.spans}
    # exactly one root query span, ended with an outcome
    roots = tr.find("query")
    assert len(roots) == 1
    root = roots[0]
    assert root.attrs["outcome"] == "ok" and root.attrs["n_patterns"] == 2
    # every query-track span hangs off the root (directly or via a rung)
    for s in tr.spans:
        if s.track == "query" and s is not root:
            p = s
            while p.parent_id is not None:
                p = by_id[p.parent_id]
            assert p is root, s.name
            assert s.async_id == root.async_id   # one Perfetto lane
    # the rung ladder: rung0..rungN-1 escalate, the last one falls back
    rungs = sorted((s for s in tr.spans if s.name.startswith("rung")),
                   key=lambda s: s.attrs["attempt"])
    assert len(rungs) >= 2
    assert all(s.attrs["outcome"] == "escalate" for s in rungs[:-1])
    assert rungs[-1].attrs["outcome"] in ("escalate", "fallback")
    # out_cap strictly escalates along the ladder
    caps_seq = [s.attrs["out_cap"] for s in rungs]
    assert caps_seq == sorted(set(caps_seq))
    if rungs[-1].attrs["outcome"] == "fallback":
        fb = tr.find("exact_fallback")
        assert fb, "fallback leg must be traced"
        # the exact run's per-cascade-step work hangs under the leg
        steps = [s for s in tr.spans if s.name.startswith("cascade_step")
                 and s.parent_id == fb[0].span_id]
        assert steps and all(s.attrs.get("kind") for s in steps)
    # each dispatch ran under a step span on the engine track
    for d in tr.find("dispatch"):
        assert by_id[d.parent_id].name == "step"
    # registry saw the same story the spans tell
    snap = reg.to_dict()
    assert snap["counters"]["serve_escalations_total"] == len(rungs) - 1
    assert snap["counters"]["serve_dispatches_total"] == len(
        tr.find("dispatch"))
    assert res.rows.shape[1] == 3     # ?x ?y ?z — the query still answers


def test_remembered_rung_marks_the_dispatch_span(rng):
    """A traced repeat of an escalated query: its one dispatch counts it
    in `remembered`, escalates nothing, and its query spans show an admit
    wait and the answering rung, with no rung0 escalate span."""
    store = build_store(random_graph(rng), 1)
    tr = Tracer()
    reg = MetricsRegistry()
    eng = ServeEngine(store, caps=TINY, max_escalations=8, tracer=tr,
                      metrics=reg)
    first = eng.execute([CHAIN])[0]
    assert all(d.attrs["remembered"] == 0 for d in tr.find("dispatch"))
    n0 = len(tr.spans)
    second = eng.execute([CHAIN])[0]
    new = tr.spans[n0:]
    disp = [s for s in new if s.name == "dispatch"]
    assert len(disp) == 1
    assert disp[0].attrs["remembered"] == disp[0].attrs["n"] == 1
    assert disp[0].attrs["escalated"] == 0
    rungs = [s for s in new if s.name.startswith("rung")]
    assert [s.attrs["outcome"] for s in rungs] == ["ok"]
    assert rungs[0].attrs["attempt"] == first.stats["attempt"] > 0
    assert [s.attrs["phase"] for s in new if s.name == "queued"] == [
        "admit"]
    assert reg.to_dict()["counters"]["serve_rung_memory_hits_total"] == 1
    assert second.rows_set() == first.rows_set()


def test_engine_trace_exports_loadable_json(rng, tmp_path):
    store = build_store(random_graph(rng), 1)
    tr = Tracer()
    eng = ServeEngine(store, caps=TINY, max_escalations=3, tracer=tr,
                      metrics=MetricsRegistry())
    eng.execute([CHAIN])
    path = tmp_path / "TRACE.json"
    tr.export(str(path))
    events = load_chrome(str(path))
    names = {e["name"] for e in events}
    assert {"query", "submit", "step", "dispatch"} <= names


# ---------------------------------------------------------------------------
# dispatch phases: launch / wait / fetch under dispatch, deliver under step
# ---------------------------------------------------------------------------

PAIR = [Pattern("?x", 101, "?y")]
ROOMY = Caps(out_cap=512, probe_cap=64, row_cap=64)


def _waited_on(monkeypatch):
    """Record the output trees the engine waits on (jax.block_until_ready
    is its only sync) and the bytes of their array leaves."""
    import jax
    seen = []
    real = jax.block_until_ready

    def wait(x):
        seen.append(sum(a.nbytes for a in jax.tree.leaves(x)))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", wait)
    return seen


def _check_phases(tr, waited):
    by_id = {s.span_id: s for s in tr.spans}
    disp = sorted(tr.find("dispatch"), key=lambda s: s.t0)
    assert disp and len(waited) == len(disp)
    for d, nbytes in zip(disp, waited):
        kids = {s.name: s for s in tr.spans if s.parent_id == d.span_id}
        launch = kids["dispatch.launch"]
        wait, fetch = kids["dispatch.wait"], kids["dispatch.fetch"]
        assert launch.t0 == d.t0 and launch.t1 == wait.t0
        assert wait.t1 == fetch.t0 and fetch.t1 <= d.t1
        assert fetch.attrs["bytes"] == nbytes > 0
        assert by_id[d.parent_id].name == "step"
    deliver = tr.find("deliver")
    assert deliver and all(by_id[s.parent_id].name == "step"
                           for s in deliver)
    return disp


def test_traced_dispatch_records_its_phases(rng, monkeypatch):
    store = build_store(random_graph(rng), 1)
    queries = [CHAIN, PAIR, CHAIN]
    plain = ServeEngine(store, caps=TINY, max_escalations=3,
                        metrics=MetricsRegistry()).execute(queries)
    waited = _waited_on(monkeypatch)
    tr = Tracer()
    reg = MetricsRegistry()
    eng = ServeEngine(store, caps=TINY, max_escalations=3, tracer=tr,
                      metrics=reg)
    traced = eng.execute(queries)
    disp = _check_phases(tr, waited)
    # one deliver per bucket, and each dispatch's copy is the host views
    # the engine cut its answers from
    assert len(tr.find("deliver")) == len(disp) == eng.dispatches
    escalated = [d.attrs["escalated"] for d in disp]
    assert sum(escalated) == reg.to_dict()["counters"][
        "serve_escalations_total"] > 0
    assert all(0 <= e <= d.attrs["n"] for e, d in zip(escalated, disp))
    for a, b in zip(plain, traced):
        assert a.rows_set() == b.rows_set() and len(a.rows) == len(b.rows)


def test_traced_dispatch_phases_on_a_mesh(rng, monkeypatch):
    store = build_store(random_graph(rng), 1)
    waited = _waited_on(monkeypatch)
    tr = Tracer()
    eng = ServeEngine(store, cfg=ExecConfig(routing="a2a"), caps=ROOMY,
                      mesh=_mesh1(), tracer=tr, metrics=MetricsRegistry())
    eng.execute([CHAIN, PAIR])
    disp = _check_phases(tr, waited)
    assert all(d.attrs["escalated"] == 0 for d in disp)


def test_untraced_dispatch_adds_no_sync(rng, monkeypatch):
    store = build_store(random_graph(rng), 1)
    waited = _waited_on(monkeypatch)
    eng = ServeEngine(store, caps=ROOMY, metrics=MetricsRegistry())
    res = eng.execute([CHAIN, PAIR])
    assert waited == [] and eng.dispatches == 2
    assert all(r.overflow == 0 for r in res)


def test_cascade_steps_carry_their_name_scope(rng):
    import re

    import jax.numpy as jnp
    store = build_store(random_graph(rng), 1)
    eng = ServeEngine(store, caps=ROOMY, metrics=MetricsRegistry())
    eng.execute([CHAIN])
    (template,) = eng._template_ids
    jitted, scratch_vars = eng._build(template, 1)
    hlo = jitted.lower(
        store.flat_keys(0), store.flat_keys(1),
        jnp.zeros((1, template.n_consts), jnp.int32),
        eng._scratch(scratch_vars, 1, template.steps[0].caps.out_cap),
    ).compile().as_text()
    scopes = set(re.findall(r"cascade/(s\d+_[a-z_]+)", hlo))
    kinds = [st.kind for st in template.steps[1:]]
    assert scopes == {"s0_scan"} | {f"s{i}_{k}"
                                    for i, k in enumerate(kinds, 1)}


# ---------------------------------------------------------------------------
# metrics-off guarantee + per-tenant SLO counters
# ---------------------------------------------------------------------------


def test_global_registry_untouched_when_disabled(rng):
    store = build_store(random_graph(rng), 1)
    before = REGISTRY.to_dict()
    eng = ServeEngine(store, caps=TINY, max_escalations=3, metrics=False)
    eng.execute([CHAIN])
    assert REGISTRY.to_dict() == before
    # and the accessor still answers (empty) instead of exploding
    assert eng.metrics() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_per_tenant_latency_histograms(rng):
    store = build_store(random_graph(rng), 1)
    reg = MetricsRegistry()
    eng = ServeEngine(store, caps=Caps(out_cap=128, probe_cap=32, row_cap=16),
                      metrics=reg, max_escalations=0)
    for tenant in ("alpha", "alpha", "beta"):
        eng.submit(CHAIN, arrival=0.0, tenant=tenant)
        eng.step(now=1.0)
    h = reg.to_dict()["histograms"]
    a = h['serve_tenant_latency_seconds{tenant="alpha"}']
    b = h['serve_tenant_latency_seconds{tenant="beta"}']
    assert a["count"] == 2 and b["count"] == 1
    assert a["p99"] >= a["p50"] > 0
    assert any(k.startswith("serve_template_latency_seconds") for k in h)
    counters = reg.to_dict()["counters"]
    assert counters['serve_requests_total{tenant="alpha"}'] == 2


# ---------------------------------------------------------------------------
# fault run: detect -> retry -> clean epoch, visible in the trace
# ---------------------------------------------------------------------------


def test_fault_run_trace_shows_detect_retry_clean(rng):
    store = build_store(random_graph(rng), 1)
    fp = FaultPlan((Fault(0, 0, "drop", epoch=0),
                    Fault(0, 0, "corrupt", epoch=1)))
    tr = Tracer()
    reg = MetricsRegistry()
    eng = ServeEngine(store, cfg=ExecConfig(routing="a2a"),
                      caps=Caps(out_cap=4096, probe_cap=16, row_cap=64),
                      mesh=_mesh1(), fault_plan=fp, tracer=tr, metrics=reg)
    res = eng.execute([CHAIN])[0]
    disp = sorted(tr.find("dispatch"), key=lambda s: s.t0)
    assert len(disp) >= 3              # two poisoned epochs + one clean
    assert disp[0].attrs["bad"] > 0 and disp[1].attrs["bad"] > 0
    assert disp[-1].attrs["bad"] == 0  # recovered on a clean epoch
    epochs = [s.attrs["epoch"] for s in disp]
    assert epochs == sorted(epochs) and len(set(epochs)) == len(epochs)
    assert all(s.attrs["retry"] == i for i, s in enumerate(disp[:3]))
    # the retries re-dispatched the same batch, visible in the registry
    c = reg.to_dict()["counters"]
    assert c["serve_faults_detected_total"] >= 2
    assert c["serve_fault_redispatches_total"] >= 2
    assert "serve_fault_unrecovered_total" not in c
    # the degenerate 1-shard mesh moves zero bytes over the collective
    # (s-1 == 0 peers), so the payload counters must not lie about it
    assert c.get("serve_a2a_probe_bytes_total", 0) == 0
    assert res.rows is not None


def test_a2a_leg_bytes_wire_format():
    from repro.core.distributed import a2a_leg_bytes
    probe, answer = a2a_leg_bytes(16, 8, 4)
    # probe leg: (s-1) peers x bucket_cap keyed slots of (key, tag) int64s
    assert probe == 3 * 16 * (8 + 8)
    # answer leg adds the cap-rows payload + validity/checksum words
    assert answer == 3 * 16 * (8 * 8 + 4 + 4)
    assert a2a_leg_bytes(16, 8, 1) == (0, 0)   # no peers, no traffic


# ---------------------------------------------------------------------------
# explain(): estimated vs actual; lifecycle logging
# ---------------------------------------------------------------------------


def test_explain_drift_column(rng):
    store = build_store(random_graph(rng), 1)
    plan = compile_plan(store, CHAIN, Caps(out_cap=128, probe_cap=32, row_cap=16))
    base = explain(plan)
    assert "drift" not in base         # golden no-stats text unchanged
    stats: list = []
    execute_local(store, plan, stats=stats)
    text = explain(plan, stats=stats)
    assert "drift=x" in text and "wall=" in text
    assert "actual=" in text and "est cost" in text
    # the no-stats render is untouched by an instrumented run existing
    assert explain(plan) == base


def test_serve_logger_lifecycle_events(rng, caplog):
    store = build_store(random_graph(rng), 1)
    eng = ServeEngine(store, caps=TINY, max_escalations=3,
                      metrics=MetricsRegistry())
    with caplog.at_level(logging.DEBUG, logger="repro.serve"):
        eng.execute([CHAIN])
    msgs = [r.message for r in caplog.records]
    assert any("admit" in m for m in msgs)
    assert any("escalat" in m for m in msgs)
    # off by default: the logger inherits WARNING and adds no handlers
    lg = logging.getLogger("repro.serve")
    assert lg.handlers == [] and lg.getEffectiveLevel() >= logging.WARNING
