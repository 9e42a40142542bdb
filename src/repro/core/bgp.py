"""BGP executors over the planner's ``PhysicalPlan`` IR (DESIGN.md §1/§6).

Planning lives in ``core/planner.py``: ``compile_plan`` turns a pattern
list into a ``PhysicalPlan`` whose steps each carry their own operator
(``scan | mapsin | multiway | reduce_side``) and static capacities
(``Caps``). Every executor here CONSUMES a plan; passing a raw
``Sequence[Pattern]`` still works — the entry points are thin
plan-then-execute wrappers. ``ExecConfig`` is runtime-only: kernel
``impl``, collective ``routing``, and the ``reorder`` escape hatch.

Execution model (the fused probe engine, this module's layer of it):
the whole cascade — the first-pattern scan plus every step — is compiled
as ONE jitted function per (plan, cfg) and cached, so ``execute_local``
pays a single dispatch per query instead of ~6 eager ops per step, and
the initial Bindings buffers are donated to the computation (active on
accelerator backends). Host syncs (``int(count())`` per step) happen
only on the opt-in ``stats=`` instrumentation path, which records the
ACTUAL row counts, the per-step overflow counters (probe/out-cap drops
— surfaced, never silent), and the measured probe->region fan-out that
feeds ``query_traffic_actual``'s routed model and the planner's a2a
capacity embedding.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.core import mapsin as ms
from repro.core import reduce_side as rs
from repro.core.plan import make_plan
from repro.core.planner import (  # noqa: F401  (re-exported API surface)
    ALL_OPERATORS, Caps, LogicalPlan, PhysicalPlan, PlanStep, _host_keys,
    compile_plan, explain, order_patterns, pattern_cardinality, quantize_cap)
from repro.core.rdf import Pattern
from repro.core.triple_store import TripleStore


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Runtime-only knobs. Capacities and planning options moved to the
    planner (``Caps`` / ``compile_plan`` arguments) — a capacity is a
    compile-time shape constant carried by the plan, not a runtime flag."""
    impl: str = "jnp"            # jnp | pallas | pallas_interpret
    routing: str = "broadcast"   # dist_probe collective: broadcast | a2a
                                 # (a2a = point-to-point region routing)
    reorder: bool = True         # False = execute patterns as given

    def __post_init__(self):
        # an unknown impl would otherwise run jnp without saying so
        if self.impl not in ("jnp", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown impl {self.impl!r}")


@dataclasses.dataclass(frozen=True)
class Step:
    """DEPRECATED legacy step (kind: scan | join | multiway). New code
    consumes ``planner.PlanStep`` (which adds per-step caps + estimates);
    this shape survives only for ``plan_steps`` callers."""
    kind: str
    patterns: tuple[Pattern, ...]


def plan_steps(patterns: Sequence[Pattern], caps: Caps | None = None,
               store: TripleStore | None = None, multiway: bool = True,
               reorder: bool = True) -> list[Step]:
    """DEPRECATED: heuristic-ordered legacy steps. Use ``compile_plan``
    (cost-based, per-step operators + caps) and read ``plan.steps``."""
    from repro.core.planner import ENGINE_OPERATORS
    plan = compile_plan(store, patterns, caps or Caps(),
                        ordering="heuristic", multiway=multiway,
                        reorder=reorder, operators=ENGINE_OPERATORS)
    kind_of = {"mapsin": "join", "reduce_side": "join"}
    return [Step(kind_of.get(st.kind, st.kind), st.patterns)
            for st in plan.steps]


def as_plan(store: TripleStore | None, query, mode: str = "mapsin",
            cfg: ExecConfig = ExecConfig(), caps: Caps = Caps(),
            num_shards: int = 0, route_shards: int = 10) -> PhysicalPlan:
    """Resolve a query argument (PhysicalPlan | LogicalPlan | patterns)
    into a PhysicalPlan — the plan-then-execute shim behind every legacy
    entry point."""
    if isinstance(query, PhysicalPlan):
        return query
    return compile_plan(store, query, caps, mode=mode,
                        reorder=cfg.reorder, routing=cfg.routing,
                        num_shards=num_shards, route_shards=route_shards)


# ---------------------------------------------------------------------------
# Traffic accounting (bytes shipped by the collectives; static formulas)
# ---------------------------------------------------------------------------


def step_traffic_bytes(step: PlanStep, mode: str, num_shards: int,
                       n_vars_before: int) -> int:
    """Global bytes crossing the interconnect for one step (padding
    included), from the step's OWN caps.

    Modes:
      mapsin         — the implemented broadcast-GET: probe keys are
                       all-gathered (correct for arbitrarily fat rows), match
                       counts all-gathered, matches psum_scattered home.
                       Pays O(S) on the key/count legs — fine for pods,
                       quantified so §Perf can show the routed win.
      mapsin_routed  — the production point-to-point GET (DESIGN.md §2):
                       each probe travels to its owner shard once (a2a) and
                       its matches travel back once. O(B) — the paper's RPC.
                       The record is two keys + origin bookkeeping; the
                       residual filters never cross the network (applied by
                       the origin shard after the round trip — see
                       _dist_probe_a2a).
      reduce         — shuffle BOTH relations (repartition join).
    """
    s, b = num_shards, step.caps.out_cap
    if s == 1 or step.kind == "scan":
        return 0
    cap = (step.caps.row_cap if step.kind == "multiway"
           else step.caps.probe_cap)
    if step.kind == "reduce_side":
        mode = "reduce"     # a hybrid plan's reduce step shuffles whatever
                            # the comparison mode prices the OTHER steps at
    if mode == "mapsin":
        keys = s * b * (8 + 8 + 24) * (s - 1)          # all_gather lo/hi/filters
        counts = s * (s * b) * 4 * (s - 1)             # all_gather counts
        matches = s * (s * b) * cap * 8                # psum_scatter ring pass
        return keys + counts + matches
    if mode == "mapsin_routed":
        keys = s * b * (8 + 8 + 4)                     # a2a probe records
        matches = s * b * cap * 8                      # a2a matches home
        return keys + matches
    # reduce-side: shuffle Omega and the scanned relation in full
    nv_left = n_vars_before
    per_rel = s * s * step.caps.bucket_cap * 4         # rows x int32 cols
    rounds = len(step.patterns)
    return rounds * (per_rel * (nv_left + 3) + per_rel)  # + validity bytes


def a2a_step_payload_bytes(bucket_cap: int, answer_cap: int,
                           num_shards: int) -> int:
    """Static per-shard a2a collective payload of ONE dist_probe round
    (DESIGN.md §2 wire format): per non-local destination, the probe
    bucket's (lo, hi) records out plus the answer return leg (answer_cap
    key slots + count + missed per bucket slot). The local diagonal block
    never crosses the network and is excluded. The ONE shared formula —
    the serving engine's traffic accounting and both benches call this,
    so a wire-format change (like PR 4's 44->20 B record) lands once;
    the per-leg split lives next to the wire format itself
    (``distributed.a2a_leg_bytes``) and feeds the probe/answer byte
    counters on dispatch spans and metrics."""
    probe, answer = dist.a2a_leg_bytes(bucket_cap, answer_cap, num_shards)
    return probe + answer


def query_traffic(query, mode: str, caps: Caps = Caps(),
                  num_shards: int = 1,
                  store: TripleStore | None = None) -> int:
    """Total modeled interconnect bytes for a query (paper's network
    metric). `query` may be a compiled PhysicalPlan or a pattern list
    (planned heuristically when no store supplies statistics)."""
    plan = as_plan(store, query, caps=caps)
    total = 0
    seen: set[str] = set()
    for st in plan.steps:
        total += step_traffic_bytes(st, mode, num_shards, len(seen))
        for p in st.patterns:
            seen.update(p.variables)
    return total


# ---------------------------------------------------------------------------
# Executors
# ---------------------------------------------------------------------------


def _cascade_body(plan: PhysicalPlan, cfg: ExecConfig):
    """The whole-cascade computation:
    (keys_spo, keys_ops, scratch) -> (Bindings, per-step overflow).

    One traced function per (plan, cfg): every step fuses into a single
    XLA computation, so repeated execution pays one dispatch and zero
    per-step host syncs. `scratch` is the zeroed initial Bindings,
    donated on backends that support donation. Each step runs the
    operator the PLANNER chose for it, at the caps the plan embeds.
    The second output is the (n_steps,) CUMULATIVE overflow counter
    after each step — a handful of scalars riding the existing dispatch,
    so overflow-escalation (DESIGN.md §7) can localize a truncation to
    its step without the instrumented run's per-step host syncs.
    """
    steps = plan.steps
    first = steps[0].patterns[0]
    first_vars = make_plan(first, ()).out_var_names

    def fn(keys_spo, keys_ops, scratch):
        keys_of = lambda pat, dom: (keys_spo if make_plan(pat, dom).index == 0
                                    else keys_ops)
        bnd = ms.scan_pattern(first, keys_of(first, ()),
                              steps[0].caps.out_cap, cfg.impl,
                              scratch=scratch)
        ovfs = [bnd.overflow]
        for st in steps[1:]:
            c = st.caps
            if st.kind == "multiway":
                keys = keys_of(st.patterns[0], bnd.vars)
                bnd = ms.multiway_step(bnd, st.patterns, keys, c.row_cap,
                                       c.out_cap, cfg.impl)
            elif st.kind == "mapsin":
                keys = keys_of(st.patterns[0], bnd.vars)
                bnd = ms.mapsin_step(bnd, st.patterns[0], keys,
                                     c.probe_cap, c.out_cap, cfg.impl)
            else:                # reduce_side: relation scanned fresh
                for pat in st.patterns:
                    bnd = rs.local_reduce_step(bnd, pat, keys_of(pat, ()),
                                               c.scan_cap, c.probe_cap,
                                               c.out_cap, cfg.impl)
            ovfs.append(bnd.overflow)
        return bnd, jnp.stack(ovfs)

    return fn, first_vars


def _compiled_cascade(store: TripleStore, plan: PhysicalPlan,
                      cfg: ExecConfig):
    key = ("cascade", plan, cfg)
    hit = store.plan_cache.get(key)
    if hit is None:
        fn, first_vars = _cascade_body(plan, cfg)
        donate = (2,) if jax.default_backend() in ("tpu", "gpu") else ()
        hit = (jax.jit(fn, donate_argnums=donate), first_vars)
        store.plan_cache[key] = hit
    return hit


def _check_plan_mode(query, mode: str):
    """A compiled plan carries its own operators, so the `mode` argument
    is only meaningful as a reduce-BASELINE request: asking for 'reduce'
    on a mapsin-compiled plan would silently time the wrong engine. (The
    default 'mapsin' with a plan means 'execute the plan as compiled' —
    hybrid plans legitimately contain reduce_side fallback steps.)"""
    if not isinstance(query, PhysicalPlan):
        return
    if mode == "reduce" and any(st.kind in ("mapsin", "multiway")
                                for st in query.steps):
        raise ValueError("mode='reduce' with a compiled mapsin plan — "
                         "operators are baked into the plan; use "
                         "compile_plan(..., mode='reduce') for the baseline")


def execute_local(store: TripleStore, query, mode: str = "mapsin",
                  cfg: ExecConfig = ExecConfig(), caps: Caps = Caps(),
                  stats: list | None = None,
                  route_shards: int | None = None):
    """Single-shard execution (functional reference; also the oracle's peer).

    `query` is a compiled ``PhysicalPlan`` or a raw pattern sequence
    (compiled cost-based on the spot — cached on the store). The default
    path runs the cached whole-cascade jit — no per-step dispatch, no
    host syncs in the timed region. When `stats` is a list (opt-in
    instrumentation, off the hot path), the cascade runs stepwise and
    appends per-step dicts with ACTUAL row counts, the per-step overflow
    counter, and the measured probe->region fan-out — feeding the
    measured traffic model in query_traffic_actual (the paper's network
    metric) and the planner's a2a capacity embedding. An explicit
    `route_shards` overrides the plan's baked-in measurement size; the
    default (None) keeps the plan's (10 when compiling patterns)."""
    _check_plan_mode(query, mode)
    plan = as_plan(store, query, mode, cfg, caps,
                   route_shards=10 if route_shards is None else route_shards)
    if (route_shards is not None and isinstance(query, PhysicalPlan)
            and plan.route_shards != route_shards):
        plan = dataclasses.replace(plan, route_shards=route_shards)
    if stats is not None:
        return _execute_local_instrumented(store, plan, cfg, stats)
    jitted, first_vars = _compiled_cascade(store, plan, cfg)
    scratch = ms.Bindings.empty(first_vars, plan.steps[0].caps.out_cap)
    bnd, step_ovf = jitted(store.flat_keys(0), store.flat_keys(1), scratch)
    # cheap unconditional per-step counters (cumulative, one scalar per
    # step): overflow-escalation can trigger and localize the truncating
    # step without the instrumented run's host syncs. Attached as a plain
    # attribute — Bindings' pytree structure (table, valid, overflow) is
    # unchanged, so every existing consumer is untouched.
    bnd.step_overflow = step_ovf
    return bnd


def _route_splits(store: TripleStore, index: int, s: int) -> np.ndarray:
    """Region boundaries for a hypothetical `s`-shard layout of the index:
    the stored splits when the store is already sharded that way, otherwise
    exactly what build_store would pick (same _shard_sorted rule)."""
    if s == store.num_shards:
        return np.asarray(store.splits(index))
    ck = ("route_splits", index, s)
    if ck not in store.plan_cache:
        from repro.core.triple_store import _shard_sorted
        keys = _host_keys(store, index)
        keys = keys[keys < np.iinfo(np.int64).max]
        _, splits, _ = _shard_sorted(keys, s)
        store.plan_cache[ck] = splits
    return store.plan_cache[ck]


def _probe_fanout(store: TripleStore, plan, bnd: ms.Bindings, s: int,
                  whole_row: bool = False) -> tuple[int, int, int]:
    """Measured routing fan-out if each probe were routed only to shards
    whose key range it intersects — the paper's region-server GET, vs the
    broadcast's n_in * S. Returns (total deliveries, max per-region load,
    max range-entry count per probe); the per-region max sizes the a2a
    per-destination probe buckets and the per-probe max sizes the answer
    return leg (planner.embed_a2a_caps)."""
    from repro.core.plan import probe_ranges, row_range
    lo, hi = (row_range if whole_row else probe_ranges)(plan, bnd.table)
    lo, hi = np.asarray(lo), np.asarray(hi)
    valid = np.asarray(bnd.valid)
    splits = _route_splits(store, plan.index, s)
    from repro.core.triple_store import range_intersects_region
    hits = range_intersects_region(lo[:, None], hi[:, None],
                                   splits[None, :-1], splits[None, 1:])
    per_region = hits[valid].sum(axis=0)
    keys = _host_keys(store, plan.index)
    lens = (np.searchsorted(keys, hi[valid])
            - np.searchsorted(keys, lo[valid]))
    return (int(per_region.sum()), int(per_region.max(initial=0)),
            int(lens.max(initial=0)))


def _execute_local_instrumented(store: TripleStore, plan: PhysicalPlan,
                                cfg: ExecConfig, stats: list):
    import time as _time
    steps = plan.steps
    keys_of = lambda pat, dom: store.flat_keys(make_plan(pat, dom).index)
    s_route = plan.route_shards
    t0 = _time.perf_counter()
    bnd = ms.scan_pattern(steps[0].patterns[0],
                          keys_of(steps[0].patterns[0], ()),
                          steps[0].caps.out_cap, cfg.impl)
    ovf_prev = int(np.asarray(bnd.overflow))
    ovf_cum = [ovf_prev]
    t1 = _time.perf_counter()
    # per-step wall stamps (t0/t1 on the perf_counter clock, wall_s the
    # delta) ride the stats dicts only on this opt-in path — the jitted
    # hot path keeps zero host syncs; obs.trace.spans_from_stats turns
    # them into per-cascade-step trace spans
    stats.append({"kind": "scan", "n_in": 0, "n_out": int(bnd.count()),
                  "nv": len(bnd.vars), "relation": int(bnd.count()),
                  "n_patterns": 1, "overflow": ovf_prev,
                  "t0": t0, "t1": t1, "wall_s": t1 - t0})
    for st in steps[1:]:
        c = st.caps
        t0 = _time.perf_counter()
        n_in, nv_in = int(bnd.count()), len(bnd.vars)
        deliveries = max_region = probe_len = 0
        if st.kind == "multiway":
            keys = keys_of(st.patterns[0], bnd.vars)
            plan0 = make_plan(st.patterns[0], bnd.vars)
            deliveries, max_region, probe_len = _probe_fanout(
                store, plan0, bnd, s_route, whole_row=True)
            bnd = ms.multiway_step(bnd, st.patterns, keys, c.row_cap,
                                   c.out_cap, cfg.impl)
        elif st.kind == "mapsin":
            keys = keys_of(st.patterns[0], bnd.vars)
            plan0 = make_plan(st.patterns[0], bnd.vars)
            deliveries, max_region, probe_len = _probe_fanout(
                store, plan0, bnd, s_route)
            bnd = ms.mapsin_step(bnd, st.patterns[0], keys, c.probe_cap,
                                 c.out_cap, cfg.impl)
        else:                    # reduce_side re-scans with an empty domain
            for pat in st.patterns:
                keys = keys_of(pat, ())
                bnd = rs.local_reduce_step(bnd, pat, keys, c.scan_cap,
                                           c.probe_cap, c.out_cap, cfg.impl)
        n_out = int(bnd.count())         # host sync: the step's work is done
        t1 = _time.perf_counter()        # before the relation-scan extras
        rel = 0
        for pat in st.patterns:
            r = ms.scan_pattern(pat, keys_of(pat, ()), c.scan_cap, cfg.impl)
            rel += int(r.count())
        ovf_now = int(np.asarray(bnd.overflow))
        stats.append({"kind": st.kind, "n_in": n_in,
                      "n_out": n_out, "nv": nv_in,
                      "relation": rel, "n_patterns": len(st.patterns),
                      "deliveries": deliveries, "route_shards": s_route,
                      "deliveries_max_region": max_region,
                      "probe_len_max": probe_len,
                      "overflow": ovf_now - ovf_prev,
                      "t0": t0, "t1": t1, "wall_s": t1 - t0})
        ovf_prev = ovf_now
        ovf_cum.append(ovf_now)
    bnd.step_overflow = jnp.asarray(ovf_cum, jnp.int32)  # same contract as
    return bnd                                           # the jitted path


def query_traffic_actual(stats: list, mode: str, num_shards: int,
                         n_triples: int = 0) -> dict:
    """Data-movement bytes from ACTUAL row counts (vs the static-capacity
    model in query_traffic). Two components, mirroring the paper's setting:

    network — what crosses the interconnect per join step:
      mapsin_routed — split-aware routing: each input mapping's probe
                      record (20 B: lo/hi keys + origin; the residual
                      filters stay on the origin shard since PR 4) travels
                      once per REGION its key range intersects — the
                      MEASURED fan-out recorded by the instrumented
                      executor ("deliveries"; ~1 for point probes, >1 only
                      for fat rows spanning region boundaries) — and each
                      match comes back once (12 B triple);
      mapsin        — broadcast-GET: 44 B probe records (lo/hi + filters +
                      origin) x (S-1), matches once;
      reduce        — Omega + the (already filtered) relation are shuffled.

    scanned — storage bytes read to produce the step's input:
      reduce        — HDFS has NO index: every pattern forces a full pass
                      over the dataset in the map phase (the dominant cost
                      the paper measures for selective queries);
      mapsin        — index GETs: ~log2(N) binary-search touches per probe
                      plus the matched entries only.
    """
    import math
    s = num_shards
    net = 0
    scanned = 0
    routed = broadcast = 0                 # probe records: routed vs x(S-1)
    logn = max(math.ceil(math.log2(max(n_triples, 2))), 1)
    for st in stats:
        rounds = 1 if st["kind"] == "multiway" else st["n_patterns"]
        if st["kind"] == "scan":
            if mode == "reduce":
                scanned += n_triples * 8          # full pass, no index
            else:
                scanned += st["n_out"] * 8 + logn * 8  # index range scan
            continue
        # a planner-selected reduce_side step shuffles and re-scans its
        # relation whatever the comparison mode — pricing it as an index
        # GET (zero probe records) would under-report hybrid plans
        if st["kind"] == "reduce_side" or mode not in ("mapsin",
                                                       "mapsin_routed"):
            row_l = st["nv"] * 4 + 4
            if s > 1:
                net += st["n_patterns"] * (st["n_in"] * row_l
                                           + st["relation"] * 16)
            scanned += st["n_patterns"] * n_triples * 8
            continue
        rec_routed, rec_bcast, match_b = 20, 44, 12
        deliv = (st["deliveries"] if st.get("route_shards") == s
                 and "deliveries" in st else st["n_in"])
        routed += deliv * rec_routed * rounds
        broadcast += st["n_in"] * rec_bcast * (s - 1) * rounds
        if mode == "mapsin_routed":
            if s > 1:
                net += deliv * rec_routed * rounds + st["n_out"] * match_b
            scanned += st["n_in"] * rounds * logn * 8 + st["n_out"] * 8
        else:  # mode == "mapsin" (broadcast probe records)
            if s > 1:
                net += (st["n_in"] * rec_bcast * (s - 1) * rounds
                        + st["n_out"] * match_b)
            scanned += st["n_in"] * rounds * logn * 8 + st["n_out"] * 8
    return {"network": net, "scanned": scanned, "total": net + scanned,
            "probe_bytes_routed": routed, "probe_bytes_broadcast": broadcast}


def apply_dist_step(bnd: ms.Bindings, st: PlanStep, keys, splits,
                    cfg: ExecConfig, axis: str, batched: bool = False,
                    fault=None, with_check: bool = False) -> ms.Bindings:
    """One distributed MAPSIN cascade step (join or multiway star) at the
    step's OWN caps — the shared dispatch behind execute_sharded's
    per-shard body and the serving engine's batched template cascade
    (`batched=True` expects Bindings with a leading query axis and routes
    the whole batch through ONE collective round per step; see
    core/distributed.py). `fault`/`with_check` hook the a2a answer-leg
    integrity machinery (serve/faults.py): with_check returns
    ``(Bindings, bad)`` and requires the batched a2a path."""
    c = st.caps
    extra = ({"fault": fault, "with_check": with_check}
             if batched and (fault is not None or with_check) else {})
    if st.kind == "multiway":
        fn = (dist.batched_dist_multiway_step if batched
              else dist.dist_multiway_step)
        return fn(bnd, st.patterns, keys, c.row_cap, c.out_cap, axis,
                  cfg.impl, shard_splits=splits, routing=cfg.routing,
                  bucket_cap=c.a2a_bucket_cap, **extra)
    fn = dist.batched_dist_mapsin_step if batched else dist.dist_mapsin_step
    return fn(bnd, st.patterns[0], keys, c.probe_cap, c.out_cap, axis,
              cfg.impl, shard_splits=splits, routing=cfg.routing,
              bucket_cap=c.a2a_bucket_cap, **extra)


def mesh_fingerprint(mesh, axis: str) -> tuple:
    """Hashable mesh identity for compile-cache keys: axis name + device
    ids in mesh order. Two meshes with the same fingerprint place the same
    shard on the same device, so a cascade compiled for one is valid for
    the other."""
    return (axis, tuple(mesh.axis_names),
            tuple(int(d.id) for d in np.ravel(mesh.devices)))


def _sharded_fn(plan: PhysicalPlan, cfg: ExecConfig, axis: str,
                splits_spo=None, splits_ops=None):
    steps = plan.steps

    def fn(keys_spo, keys_ops):
        keys_spo = keys_spo.reshape(-1)
        keys_ops = keys_ops.reshape(-1)
        keys_of = lambda pat, dom: (keys_spo if make_plan(pat, dom).index == 0
                                    else keys_ops)
        splits_of = lambda pat, dom: (splits_spo
                                      if make_plan(pat, dom).index == 0
                                      else splits_ops)
        bnd = ms.scan_pattern(steps[0].patterns[0],
                              keys_of(steps[0].patterns[0], ()),
                              steps[0].caps.out_cap, cfg.impl)
        for st in steps[1:]:
            c = st.caps
            if st.kind in ("mapsin", "multiway"):
                keys = keys_of(st.patterns[0], bnd.vars)
                bnd = apply_dist_step(
                    bnd, st, keys, splits_of(st.patterns[0], bnd.vars),
                    cfg, axis)
            else:
                for pat in st.patterns:
                    keys = keys_of(pat, ())  # relation scan: empty domain
                    bnd = rs.dist_reduce_step(bnd, pat, keys, c.scan_cap,
                                              c.bucket_cap, c.probe_cap,
                                              c.out_cap, axis, cfg.impl)
        return bnd.table, bnd.valid, bnd.overflow[None]
    return fn


def execute_sharded(store: TripleStore, query, mesh, mode: str = "mapsin",
                    cfg: ExecConfig = ExecConfig(), axis: str = "data",
                    routing: str | None = None, caps: Caps = Caps()):
    """Distributed execution under shard_map on `mesh` (store sharded on
    `axis`). `query` is a PhysicalPlan or a pattern sequence (compiled
    cost-based with num_shards = the mesh size, so a2a capacities are
    embedded from measurement at compile time — the planner subsumes the
    old tune_a2a_bucket_cap call). Probes are routed via the stored
    region splits: with cfg.routing == "broadcast" every shard sees every
    probe and answers only ranges intersecting its slice; with "a2a" each
    probe record is shipped point-to-point to exactly the intersecting
    shards (dist._dist_probe_a2a). `routing` overrides cfg.routing when
    given. Returns (table (S*cap, nv), valid, overflow (S,), vars)."""
    if routing is not None:
        cfg = dataclasses.replace(cfg, routing=routing)
    _check_plan_mode(query, mode)
    s = int(mesh.shape[axis])
    plan = as_plan(store, query, mode, cfg, caps, num_shards=s)
    if (cfg.routing == "a2a"
            and any(st.kind in ("mapsin", "multiway")
                    and st.caps.a2a_bucket_cap == 0
                    for st in plan.steps[1:])):
        # pre-compiled plan without embedded a2a caps: embed now, with the
        # drop-free bound read off the plan's OWN steps (caps=None) — the
        # `caps` argument only parameterizes pattern-list compilation
        from repro.core.planner import embed_a2a_caps
        plan = embed_a2a_caps(store, plan, None, s)
    # cache the jitted shard_map per (plan, cfg, mesh): a fresh closure
    # every call would defeat jax's jit cache (keyed on function identity)
    # and re-trace + re-compile on each execution
    ck = ("sharded", plan, cfg, axis, mesh)
    jitted = store.plan_cache.get(ck)
    if jitted is None:
        fn = _sharded_fn(plan, cfg, axis,
                         splits_spo=np.asarray(store.splits_spo),
                         splits_ops=np.asarray(store.splits_ops))
        sharded = shard_map(
            fn, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None)),
            out_specs=(P(axis, None), P(axis), P(axis)),
            check_rep=False)
        jitted = jax.jit(sharded)
        store.plan_cache[ck] = jitted
    table, valid, overflow = jitted(store.keys_spo, store.keys_ops)
    return table, valid, overflow, plan.var_order


def rows_set(table, valid, n_vars: int) -> set[tuple[int, ...]]:
    """Materialize valid rows as a python set (host-side, for comparisons)."""
    t = np.asarray(table)[np.asarray(valid)]
    if n_vars == 0:
        return set([()] if len(t) else [])
    return set(map(tuple, t[:, :n_vars].tolist()))
