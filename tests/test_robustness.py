"""Robustness layer (DESIGN.md §7): overflow-escalation retries along the
quantize_cap grid with the exact reduce_side fallback, per-query deadlines
(queued / mid-dispatch / during-escalation), graceful degradation
(priority shedding, EngineBusy payload, bounded-inexact mode), and the
seeded fault-injection harness with answer-leg checksum detection.

The fast tier covers the a2a fault hooks on a degenerate 1-device mesh
(the collective + checksum code paths are identical at any shard count);
test_multidevice.py runs the 8-device chaos case."""
import numpy as np
import pytest

from repro.core import (Caps, ExecConfig, Pattern, build_store,
                        execute_local, execute_oracle, rows_set)
from repro.core.planner import escalate_caps, next_cap, quantize_cap
from repro.obs import MetricsRegistry
from repro.serve import (EngineBusy, Fault, FaultPlan, QueryShed,
                         QueryTimeout, ServeEngine)

CAPS = Caps(scan_cap=4096, out_cap=4096, probe_cap=16, row_cap=64)
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
# cap escalation on the quantize_cap grid
# ---------------------------------------------------------------------------


def test_next_cap_lands_on_grid_from_both_families():
    # from 3*2^(k-1) grid points the successor is 2^(k+1), never 2^k
    assert next_cap(12) == 16 and next_cap(24) == 32 and next_cap(48) == 64
    # from powers of two: the next power of two
    assert next_cap(8) == 16 and next_cap(16) == 32 and next_cap(1024) == 2048
    # floor of the grid
    assert next_cap(1) == 8 and next_cap(0) == 8


def test_escalation_chain_never_repeats_and_stays_on_grid():
    caps = TINY
    seen = set()
    for _ in range(12):
        caps = escalate_caps(caps)
        for dim in ("scan_cap", "probe_cap", "row_cap", "out_cap"):
            v = getattr(caps, dim)
            assert quantize_cap(v) == v            # on the quantize grid
            assert (dim, v) not in seen            # strictly increasing
            seen.add((dim, v))
        assert caps.a2a_bucket_cap == 0            # re-embedded per budget


def test_escalation_is_geometric():
    c1 = escalate_caps(TINY)
    assert (c1.out_cap, c1.probe_cap, c1.row_cap, c1.scan_cap) == (
        16, 8, 8, 8192)
    c2 = escalate_caps(c1)
    assert (c2.out_cap, c2.probe_cap, c2.row_cap) == (32, 16, 16)


# ---------------------------------------------------------------------------
# overflow-escalation retries: exactness under undersized caps
# ---------------------------------------------------------------------------


def test_heavy_hitter_escalation_matches_oracle(rng):
    """The acceptance case: deliberately undersized caps, yet the engine
    returns row sets bit-identical to the execute_local oracle — no
    silent truncation survives escalation."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    want, ovars = execute_oracle(tr, CHAIN)
    assert len(want) > TINY.out_cap                # genuinely heavy
    eng = ServeEngine(store, caps=TINY)
    res = eng.execute([CHAIN])[0]
    assert res.rows_set(ovars) == want
    assert res.overflow == 0
    assert eng.escalations + eng.fallbacks > 0     # it actually escalated
    bnd = execute_local(store, CHAIN, "mapsin", caps=CAPS)
    assert res.rows_set(bnd.vars) == rows_set(bnd.table, bnd.valid,
                                              len(bnd.vars))


def test_attempt_bound_terminates_at_reduce_side_fallback(rng):
    """max_escalations=1: the very first overflow goes straight to the
    unrestricted planner's exact fallback — within the attempt bound."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    want, ovars = execute_oracle(tr, CHAIN)
    eng = ServeEngine(store, caps=TINY, max_escalations=1)
    res = eng.execute([CHAIN])[0]
    assert res.stats["fallback"] == "reduce_side"
    assert res.stats["attempt"] == 0               # no batched retries
    assert res.rows_set(ovars) == want and res.overflow == 0
    assert eng.fallbacks == 1 and eng.escalations == 0


def test_escalations_bounded_then_exact(rng):
    tr = random_graph(rng)
    store = build_store(tr, 1)
    eng = ServeEngine(store, caps=TINY, max_escalations=3)
    res = eng.execute([CHAIN])[0]
    assert eng.escalations <= 2                    # attempts 1..max-1
    assert res.overflow == 0


def test_escalated_templates_reuse_compile_cache(rng):
    """A second identical heavy query enters at its remembered rung and
    compiles nothing new: escalated plans ride the same LRU caches."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    eng = ServeEngine(store, caps=TINY)
    eng.execute([CHAIN])
    compiled = len(eng._compiled)
    d0 = eng.dispatches
    eng.execute([CHAIN])
    assert len(eng._compiled) == compiled          # all cache hits
    assert eng.dispatches > d0                     # but it did re-dispatch


def test_escalation_off_preserves_truncating_behavior(rng):
    tr = random_graph(rng)
    store = build_store(tr, 1)
    want, _ = execute_oracle(tr, CHAIN)
    eng = ServeEngine(store, caps=TINY, max_escalations=0)
    res = eng.execute([CHAIN])[0]
    assert res.overflow > 0 and len(res.rows) < len(want)
    assert sum(res.stats["overflow_per_step"]) == res.overflow


def test_bounded_inexact_mode_serves_capped_with_counters(rng):
    """inexact_ok: explicit opt-in serves the capped result with the
    overflow counters attached (stats['degraded']) instead of escalating
    or shedding."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    eng = ServeEngine(store, caps=TINY)
    rid = eng.submit(CHAIN, inexact_ok=True)
    res = eng.drain()
    assert len(res) == 1 and res[0].request_id == rid
    assert res[0].overflow > 0
    assert res[0].stats["degraded"] is True
    assert sum(res[0].stats["overflow_per_step"]) == res[0].overflow
    assert eng.escalations == 0 and eng.fallbacks == 0


# ---------------------------------------------------------------------------
# rung memory: a repeat of an escalated query enters at the rung it needed
# ---------------------------------------------------------------------------


def _rung_hits(reg) -> float:
    return reg.counter("serve_rung_memory_hits_total").value


@pytest.mark.parametrize("mesh", [False, True], ids=["local", "mesh_a2a"])
def test_rung_memory_answers_a_repeat_at_its_rung(rng, mesh):
    """The first execute climbs the ladder; the second dispatches once, at
    the remembered rung, on the cascade the ladder compiled — no
    escalation, same rows, equal to the oracle. On the a2a mesh the
    remembered budget reuses the escalated signature (re-embedded a2a
    caps), not a new one."""
    tr = random_graph(rng, n=150)
    store = build_store(tr, 1)
    want, ovars = execute_oracle(tr, CHAIN)
    reg = MetricsRegistry()
    kw = (dict(cfg=ExecConfig(routing="a2a"), mesh=_mesh1()) if mesh
          else {})
    eng = ServeEngine(store, caps=TINY, max_escalations=8, metrics=reg,
                      **kw)
    first = eng.execute([CHAIN])[0]
    assert eng.escalations > 0 and eng.fallbacks == 0
    assert _rung_hits(reg) == 0
    e0, d0, sigs = eng.escalations, eng.dispatches, len(eng._signatures)
    compiled = len(eng._compiled)
    second = eng.execute([CHAIN])[0]
    assert eng.escalations == e0                   # no re-climb
    assert eng.dispatches == d0 + 1                # one dispatch, at the rung
    assert _rung_hits(reg) == 1
    assert len(eng._compiled) == compiled          # the ladder's cascade
    assert len(eng._signatures) == sigs            # and its signature
    assert second.stats["attempt"] == first.stats["attempt"] > 0
    assert second.overflow == first.overflow == 0
    assert second.rows_set(ovars) == first.rows_set(ovars) == want
    assert len(second.rows) == len(first.rows)


def test_rung_memory_starts_empty_after_an_ingest(rng, tmp_path):
    """An ingest bumps store_version, which is part of the memory's key:
    the repeat climbs the whole ladder again."""
    from repro.store import MutableTripleStore
    tr = np.unique(random_graph(rng), axis=0)
    reg = MetricsRegistry()
    st = MutableTripleStore.create(str(tmp_path / "s"), num_shards=1,
                                   overlay_limit=4096)
    st.ingest(tr)
    eng = ServeEngine(st, caps=TINY, max_escalations=8, metrics=reg)
    eng.execute([CHAIN])
    climbed = eng.escalations
    assert climbed > 0
    extra = np.array([[0, 104, 0]], np.int32)      # answer unchanged
    st.ingest(extra)
    res = eng.execute([CHAIN])[0]
    assert eng.escalations == 2 * climbed          # the same climb again
    assert _rung_hits(reg) == 0
    want, ovars = execute_oracle(np.concatenate([tr, extra]), CHAIN)
    assert res.rows_set(ovars) == want and res.overflow == 0
    st.close()


def test_rung_memory_not_read_by_inexact_requests(rng):
    """An inexact_ok submit of a remembered query runs at the base budget
    and is served degraded, as on an engine that never saw it climb."""
    store = build_store(random_graph(rng), 1)
    reg = MetricsRegistry()
    eng = ServeEngine(store, caps=TINY, max_escalations=8, metrics=reg)
    eng.execute([CHAIN])
    e0 = eng.escalations
    eng.submit(CHAIN, inexact_ok=True)
    res = eng.drain()[0]
    fresh = ServeEngine(store, caps=TINY, metrics=MetricsRegistry())
    fresh.submit(CHAIN, inexact_ok=True)
    want = fresh.drain()[0]
    assert res.stats["degraded"] is True and res.stats["attempt"] == 0
    assert res.overflow == want.overflow > 0
    assert res.rows_set() == want.rows_set()
    assert eng.escalations == e0 and _rung_hits(reg) == 0


def test_rung_memory_keeps_the_ladder_top(rng):
    """A query whose ladder ends in the exact fallback is remembered at the
    last engine rung: the repeat dispatches there once, overflows, and
    falls back at the same budget as without the memory, with no
    escalation counted."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    want, ovars = execute_oracle(tr, CHAIN)
    reg = MetricsRegistry()
    eng = ServeEngine(store, caps=TINY, max_escalations=3, metrics=reg)
    first = eng.execute([CHAIN])[0]                # no memory yet
    assert first.stats["fallback"] == "reduce_side"
    assert eng.escalations == 2 and eng.fallbacks == 1
    d0 = eng.dispatches
    second = eng.execute([CHAIN])[0]
    assert second.stats["fallback"] == "reduce_side"
    assert second.stats["attempt"] == first.stats["attempt"] == 2
    assert second.stats["caps"] == first.stats["caps"]
    assert eng.dispatches == d0 + 1 and eng.fallbacks == 2
    assert eng.escalations == 2 and _rung_hits(reg) == 1
    assert second.rows_set(ovars) == first.rows_set(ovars) == want


def test_rung_memory_keys_on_the_constants(rng):
    """One template, two constants: the heavy one climbs and is
    remembered, its light siblings keep the base caps and still share one
    base-budget dispatch."""
    tr = np.array([(1, 101, 10 + y) for y in range(6)]
                  + [(10 + y, 102, 50 + z) for y in range(6)
                     for z in range(5)]
                  + [(2, 101, 30), (30, 102, 60), (3, 101, 31),
                     (31, 102, 61)], np.int32)
    heavy = [Pattern(1, 101, "?y"), Pattern("?y", 102, "?z")]
    light = [[Pattern(c, 101, "?y"), Pattern("?y", 102, "?z")]
             for c in (2, 3)]
    store = build_store(tr, 1)
    reg = MetricsRegistry()
    eng = ServeEngine(store, caps=TINY, max_escalations=8, metrics=reg)
    base_tid = eng._signature_for(tuple(heavy), TINY)[0]
    assert all(eng._signature_for(tuple(q), TINY)[0] == base_tid
               for q in light)                     # one template
    assert eng.execute([heavy])[0].stats["attempt"] > 0
    e0, d0 = eng.escalations, eng.dispatches
    res = eng.execute(light)
    assert eng.dispatches == d0 + 1                # batched together
    assert [r.stats["attempt"] for r in res] == [0, 0]
    assert eng.escalations == e0 and _rung_hits(reg) == 0
    for q, r in zip(light, res):
        want, ovars = execute_oracle(tr, q)
        assert r.rows_set(ovars) == want and r.overflow == 0
    assert eng.execute([heavy])[0].stats["attempt"] > 0
    assert eng.escalations == e0 and _rung_hits(reg) == 1


# ---------------------------------------------------------------------------
# deadlines: queued / mid-dispatch / during escalation
# ---------------------------------------------------------------------------


def test_deadline_expired_while_queued(rng):
    store = build_store(random_graph(rng), 1)
    eng = ServeEngine(store, caps=CAPS)
    rid = eng.submit(CHAIN, arrival=0.0, deadline_s=0.5)
    out = eng.step(now=1.0)
    assert len(out) == 1 and isinstance(out[0], QueryTimeout)
    t = out[0]
    assert t.request_id == rid and t.phase == "queued"
    assert t.rows.shape[0] == 0                    # shed, never truncated
    assert t.deadline_s == 0.5 and t.waited_s == pytest.approx(1.0)
    assert eng.pending() == 0 and eng.dispatches == 0


def test_deadline_expired_mid_dispatch(rng):
    """A delay fault stalls the dispatch past the deadline: the finished
    batch's rows are DISCARDED for that query — a QueryTimeout with the
    attempt's partial stats, never a late result delivered as complete."""
    store = build_store(random_graph(rng), 1)
    fp = FaultPlan((Fault(0, 0, "delay", epoch=0, delay_s=10.0),),
                   period=1 << 20)
    eng = ServeEngine(store, cfg=ExecConfig(routing="a2a"), caps=CAPS,
                      mesh=_mesh1(), fault_plan=fp)
    rid = eng.submit(CHAIN, arrival=0.0, deadline_s=5.0)
    out = eng.step(now=0.0)
    assert len(out) == 1 and isinstance(out[0], QueryTimeout)
    t = out[0]
    assert t.request_id == rid and t.phase == "dispatch"
    assert t.rows.shape[0] == 0
    assert "overflow_per_step" in t.stats          # the attempt's counters
    assert eng.dispatches == 1                     # it DID run


def test_deadline_expired_during_escalation_retry(rng):
    tr = random_graph(rng)
    store = build_store(tr, 1)
    eng = ServeEngine(store, caps=TINY)
    rid = eng.submit(CHAIN, arrival=0.0, deadline_s=1e6)
    out = eng.step(now=0.0)                        # overflows -> re-enqueued
    assert out == [] and eng.pending() == 1
    assert eng.escalations == 1
    out = eng.step(now=2e6)                        # expires before retry
    assert len(out) == 1 and isinstance(out[0], QueryTimeout)
    t = out[0]
    assert t.request_id == rid and t.phase == "escalation"
    assert t.rows.shape[0] == 0
    # partial-stats payload: the last completed attempt's counters
    assert t.stats is not None and sum(t.stats["overflow_per_step"]) > 0
    assert t.stats["attempt"] == 0


def test_dispatch_watchdog(rng):
    store = build_store(random_graph(rng), 1)
    fp = FaultPlan((Fault(0, 0, "delay", epoch=0, delay_s=60.0),),
                   period=1 << 20)
    eng = ServeEngine(store, cfg=ExecConfig(routing="a2a"), caps=CAPS,
                      mesh=_mesh1(), fault_plan=fp, dispatch_timeout_s=5.0)
    eng.submit(CHAIN)
    out = eng.step()
    assert len(out) == 1 and isinstance(out[0], QueryTimeout)
    assert out[0].phase == "dispatch"


# ---------------------------------------------------------------------------
# graceful degradation: EngineBusy payload + priority shedding
# ---------------------------------------------------------------------------


def test_engine_busy_returns_plan_and_retry_after(rng):
    store = build_store(random_graph(rng), 1)
    eng = ServeEngine(store, caps=CAPS, max_queue=2)
    eng.execute([CHAIN])                           # time one dispatch
    assert eng._service_ewma > 0.0
    eng.submit([Pattern("?x", 101, 7)])
    eng.submit([Pattern("?x", 101, 8)])
    with pytest.raises(EngineBusy) as ei:
        eng.submit(CHAIN)
    busy = ei.value
    assert busy.plan is not None                   # planning work returned
    assert tuple(busy.plan.patterns) == tuple(CHAIN)
    assert busy.retry_after > 0.0                  # measured-service hint
    # the returned plan resubmits directly (skips replanning) once drained
    eng.drain()
    rid = eng.submit(busy.plan)
    assert [r.request_id for r in eng.drain()] == [rid]


def test_priority_shedding_with_tenant_accounting(rng):
    store = build_store(random_graph(rng), 1)
    eng = ServeEngine(store, caps=CAPS, max_queue=2)
    ra = eng.submit([Pattern("?x", 101, 7)], tenant="bulk", priority=0)
    rb = eng.submit([Pattern("?x", 101, 8)], tenant="bulk", priority=0)
    rc = eng.submit([Pattern("?x", 101, 9)], tenant="paid", priority=5)
    res = eng.drain()
    shed = [r for r in res if isinstance(r, QueryShed)]
    # the lowest-priority, most recently enqueued request was evicted
    assert len(shed) == 1 and shed[0].request_id == rb
    assert shed[0].retry_after >= 0.0
    assert eng.shed_by_tenant == {"bulk": 1}
    # every submit got exactly one result; the high-priority one has rows
    assert {r.request_id for r in res} == {ra, rb, rc}
    served = {r.request_id for r in res if not isinstance(r, QueryShed)}
    assert served == {ra, rc}


def test_equal_priority_still_raises_busy(rng):
    store = build_store(random_graph(rng), 1)
    eng = ServeEngine(store, caps=CAPS, max_queue=1)
    eng.submit([Pattern("?x", 101, 7)], priority=3)
    with pytest.raises(EngineBusy):
        eng.submit([Pattern("?x", 101, 8)], priority=3)


# ---------------------------------------------------------------------------
# fault injection + answer-leg checksums (fast tier: 1-device a2a mesh)
# ---------------------------------------------------------------------------


def test_fault_plan_is_deterministic_and_hashable():
    a = FaultPlan.sample(7, num_shards=8, n_steps=2, rate=0.05, horizon=32)
    b = FaultPlan.sample(7, num_shards=8, n_steps=2, rate=0.05, horizon=32)
    assert a == b and hash(a) == hash(b)
    assert a != FaultPlan.sample(8, num_shards=8, n_steps=2, rate=0.05,
                                 horizon=32)
    n_legs = 32 * 2 * 8
    assert 0 < len(a.faults) < 0.2 * n_legs        # ~5% of legs
    sel = a.selection(3, 2)
    assert len(sel) == 2 and all(len(s) == 2 for s in sel)
    # period wraps: epoch k and k+horizon see the same faults
    assert a.at(3, 0) == a.at(3 + 32, 0)


def test_bad_fault_kind_rejected():
    with pytest.raises(ValueError):
        Fault(0, 0, "meteor")


def test_fault_plan_requires_a2a_mesh(rng):
    store = build_store(random_graph(rng), 1)
    fp = FaultPlan((Fault(0, 0, "drop"),))
    with pytest.raises(ValueError):
        ServeEngine(store, caps=CAPS, fault_plan=fp)   # no mesh
    with pytest.raises(ValueError):
        ServeEngine(store, caps=CAPS, mesh=_mesh1(), fault_plan=fp)


def test_drop_and_corrupt_detected_retried_rows_identical(rng):
    """The chaos invariant on the fast tier: one dropped and one
    corrupted answer leg are detected by the checksums, the dispatch is
    retried onto a clean epoch, and the delivered rows are identical to
    execute_local — zero wrong rows."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    bnd = execute_local(store, CHAIN, "mapsin", caps=CAPS)
    want = rows_set(bnd.table, bnd.valid, len(bnd.vars))
    assert len(want) > 0
    fp = FaultPlan((Fault(0, 0, "drop", epoch=0),
                    Fault(0, 0, "corrupt", epoch=1)))
    eng = ServeEngine(store, cfg=ExecConfig(routing="a2a"), caps=CAPS,
                      mesh=_mesh1(), fault_plan=fp)
    res = eng.execute([CHAIN])[0]
    assert res.rows_set(bnd.vars) == want
    assert eng.corrupt_detected >= 2               # both bad legs seen
    assert eng.fault_redispatches == 2             # retried past both
    assert "fault_unrecovered" not in (res.stats or {})


def test_checked_clean_path_identical_and_unretried(rng):
    tr = random_graph(rng)
    store = build_store(tr, 1)
    bnd = execute_local(store, CHAIN, "mapsin", caps=CAPS)
    want = rows_set(bnd.table, bnd.valid, len(bnd.vars))
    eng = ServeEngine(store, cfg=ExecConfig(routing="a2a"), caps=CAPS,
                      mesh=_mesh1(), check_answers=True)
    res = eng.execute([CHAIN])[0]
    assert res.rows_set(bnd.vars) == want
    assert eng.fault_redispatches == 0 and eng.corrupt_detected == 0


def test_unrecovered_fault_never_returns_wrong_rows(rng):
    """Faults on EVERY epoch exhaust the retry budget: the result is
    flagged fault_unrecovered and its surviving rows are a SUBSET of the
    truth (quarantined blocks zeroed) — wrong rows are impossible."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    bnd = execute_local(store, CHAIN, "mapsin", caps=CAPS)
    want = rows_set(bnd.table, bnd.valid, len(bnd.vars))
    fp = FaultPlan(tuple(Fault(0, 0, "corrupt", epoch=e)
                         for e in range(64)), period=64)
    eng = ServeEngine(store, cfg=ExecConfig(routing="a2a"), caps=CAPS,
                      mesh=_mesh1(), fault_plan=fp, fault_retries=2,
                      max_escalations=0)
    res = eng.execute([CHAIN])[0]
    assert res.stats["fault_unrecovered"] is True
    assert res.rows_set(bnd.vars) <= want          # never a wrong row
    assert eng.fault_redispatches == 2             # budget exhausted


# ---------------------------------------------------------------------------
# satellite: unconditional per-step overflow on the plain local path
# ---------------------------------------------------------------------------


def test_step_overflow_flows_without_stats_instrumentation(rng):
    """execute_local's default (un-instrumented, no host syncs in the
    cascade) now attaches the cumulative per-step overflow scalars —
    escalation can localize the truncating step without stats=."""
    tr = random_graph(rng)
    store = build_store(tr, 1)
    bnd = execute_local(store, CHAIN, "mapsin", caps=TINY)
    assert hasattr(bnd, "step_overflow")
    plain = np.asarray(bnd.step_overflow)
    stats = []
    inst = execute_local(store, CHAIN, "mapsin", caps=TINY, stats=stats)
    assert plain.tolist() == np.asarray(inst.step_overflow).tolist()
    assert plain.shape[0] == len(stats)
    assert int(plain[-1]) == int(bnd.overflow)     # cumulative, total last
