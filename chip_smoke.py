"""Bring-up run of the SPARQL serving path on a TPU.

    python3 chip_smoke.py                 # one chip: phases 1-6
    python3 chip_smoke.py --chips 4       # region sharding over four chips

One process drives every phase (a chip belongs to one process at a time):

1. device   — the run needs a TPU: on any other platform it exits nonzero
              before doing any work, and it never falls back to the CPU;
2. load     — a LUBM-shaped store from ``--seed`` at the largest scale the
              21-bit term ids allow with margin, built as
              ``examples/sparql_lubm.py`` builds it;
3. serve    — LUBM queries as SPARQL text, and a burst of
              randomized-constant requests batched with them, through a
              ``ServeEngine(store, dictionary)`` with default exactness
              and caps (overflow climbs the escalation ladder);
4. kernels  — the same queries through ``execute_local`` with the Pallas
              kernels compiled (``impl="pallas"``), equal to ``impl="jnp"``;
5. durable  — a ``MutableTripleStore`` ingested across a flush, reopened
              (recovery) and served by a default ``ServeEngine``;
6. the last line of standard output is ``{"ok": true, "device": ...}``.

Every row set is checked against ``reference_rows``, an independent numpy
hash/merge join over the raw triples. A mismatch, a degraded or
incomplete answer, or any exception ends the run nonzero with no result
line. With ``--chips 4`` only the sharded path runs: the store split into
four regions on a mesh of four chips, served with a2a routing and checked
against the reference and one-device ``execute_local``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
for _path in (HERE, os.path.join(HERE, "src")):   # benchmarks/, repro
    if _path not in sys.path:
        sys.path.insert(0, _path)

# LUBM queries served here; the class scans Q6/Q14 stay out (their dense
# fallback does not fit the chip yet)
QUERIES = ("Q1", "Q3", "Q4", "Q5", "Q7", "Q8", "Q11", "Q13")
# lubm_like mints ~4,274 terms per university against 2^21 - 1 ids: 400
# universities use 82% of the id space (490 would exhaust it)
DEFAULT_UNIVERSITIES = 400
# randomized-constant requests queued with the phase-3 LUBM queries
DEFAULT_BURST = 64


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileWatch:
    """Seconds spent in XLA backend compiles and persistent-cache hits,
    from JAX's monitoring events (process-wide listeners)."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def line(self) -> str:
        return (f"compile_s={self.compile_s:.2f} "
                f"cache_hits={self.cache_hits} "
                f"cache_misses={self.cache_misses}")


# --------------------------------------------------------------------------
# the independent reference
# --------------------------------------------------------------------------


def _relation(triples: np.ndarray, pattern) -> tuple[tuple, np.ndarray]:
    """(variables, distinct (n, k) int64 bindings) of one triple pattern."""
    mask = np.ones(len(triples), bool)
    cols: dict[str, int] = {}
    for pos, term in enumerate((pattern.s, pattern.p, pattern.o)):
        col = triples[:, pos]
        if isinstance(term, str):
            if term in cols:
                mask &= col == triples[:, cols[term]]
            else:
                cols[term] = pos
        else:
            mask &= col == int(term)
    vars_ = tuple(cols)
    data = triples[mask][:, [cols[v] for v in vars_]].astype(np.int64)
    _, first = np.unique(_join_key(data, range(len(vars_))),
                         return_index=True)    # distinct bindings
    return vars_, data[first]


def _join_key(data: np.ndarray, idx) -> np.ndarray:
    key = np.zeros(len(data), np.int64)
    for i in idx:                       # ids < 2^21: three fit in 63 bits
        key = (key << 21) | data[:, i]
    return key


def _join(a, b):
    """Sort-merge join of two relations on their shared variables."""
    (va, da), (vb, db) = a, b
    shared = [v for v in va if v in vb]
    ka = _join_key(da, [va.index(v) for v in shared])
    kb = _join_key(db, [vb.index(v) for v in shared])
    order = np.argsort(kb, kind="stable")
    kb = kb[order]
    lo = np.searchsorted(kb, ka, "left")
    cnt = np.searchsorted(kb, ka, "right") - lo
    ia = np.repeat(np.arange(len(da)), cnt)
    offs = np.arange(int(cnt.sum())) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    ib = order[np.repeat(lo, cnt) + offs]
    extra = [i for i, v in enumerate(vb) if v not in va]
    return (va + tuple(vb[i] for i in extra),
            np.concatenate([da[ia], db[ib][:, extra]], axis=1))


def reference_rows(triples: np.ndarray, patterns,
                   var_order) -> set[tuple[int, ...]]:
    """Answer a BGP by vectorized joins over the raw (N, 3) id triples:
    each pattern selects its relation with numpy masks, and relations are
    joined smallest-first, preferring ones that share a variable with what
    is already joined. Independent of the engine's store, planner and
    kernels. Returns the distinct solutions in `var_order`."""
    rels = sorted((_relation(triples, p) for p in patterns),
                  key=lambda r: len(r[1]))
    acc = rels.pop(0)
    while rels:
        linked = [r for r in rels if set(r[0]) & set(acc[0])] or rels
        nxt = min(linked, key=lambda r: len(r[1]))
        rels.remove(nxt)
        acc = _join(acc, nxt)
    vars_, data = acc
    perm = [vars_.index(v) for v in var_order]
    return set(map(tuple, data[:, perm].tolist()))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    """Phase 1: a TPU with at least `chips` devices, or exit nonzero."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {d0.platform!r} "
                 f"({len(devs)} device(s)); not running on the CPU")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devs)}")
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    log(f"[device] kind={d0.device_kind} count={len(devs)} "
        f"jax={jax.__version__} libtpu={libtpu}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_load(n_universities: int, seed: int, num_shards: int = 1):
    """Phase 2: generate LUBM-like data and build the store on the
    device. Returns (triples, dictionary, store)."""
    from repro.core import build_store
    from repro.core.rdf import MAX_ID
    from repro.data import lubm_like
    t0 = time.perf_counter()
    triples, d, _ = lubm_like(n_universities, seed=seed)
    t1 = time.perf_counter()
    store = build_store(triples, num_shards=num_shards)
    jax.block_until_ready((store.keys_spo, store.keys_ops))
    t2 = time.perf_counter()
    log(f"[load] lubm_like({n_universities}, seed={seed}): "
        f"triples={len(triples)} terms={len(d)} "
        f"({len(d) / MAX_ID:.1%} of the 2^21-1 term ids) "
        f"shards={num_shards}")
    log(f"[load] cut: {n_universities} universities — term ids are 21 "
        f"bits, so the id space caps the store near 490 universities")
    log(f"[load] index bytes on device={store.storage_bytes()} "
        f"generate_s={t1 - t0:.2f} build_s={t2 - t1:.2f}")
    return triples, d, store


def _plan_line(name: str, res, n_ref: int) -> str:
    st = res.stats or {}
    ops = "fallback:" + st["fallback"] if "fallback" in st else \
        "/".join(st.get("kinds", ()))
    complete = (res.overflow == 0 and not st.get("degraded")
                and not st.get("fault_unrecovered"))
    return (f"{name:10s} ops={ops} rows={len(res.rows)} ref={n_ref} "
            f"escalations={st.get('attempt', 0)} complete={complete}")


def check_result(name: str, res, triples: np.ndarray, patterns) -> None:
    """Hold one served answer to the reference: same rows, complete."""
    from repro.serve import QueryShed, QueryTimeout
    if isinstance(res, (QueryTimeout, QueryShed)):
        raise RuntimeError(f"{name}: {type(res).__name__}")
    want = reference_rows(triples, patterns, res.vars)
    got = res.rows_set()
    line = _plan_line(name, res, len(want))
    log("  " + line)
    if not line.endswith("complete=True"):
        raise RuntimeError(f"{name}: incomplete answer")
    if got != want or len(res.rows) != len(want):
        raise RuntimeError(f"{name}: {len(res.rows)} rows served, "
                           f"{len(want)} in the reference "
                           f"({len(got ^ want)} differ)")


def phase_serve(store, d, triples: np.ndarray, n_universities: int,
                burst: int, seed: int, tag: str = "serve") -> None:
    """Phase 3: the LUBM queries as SPARQL text together with a burst of
    `burst` randomized-constant requests, all queued before the engine
    drains them — a LUBM query rides its template's batch — through a
    ServeEngine with default exactness and caps (overflow escalates, then
    falls back to an exact plan)."""
    from benchmarks.bench_serving import _lubm_shapes
    from repro.data.rdf_gen import LUBM_SPARQL
    from repro.serve import ServeEngine, parse_bgp
    eng = ServeEngine(store, d)
    rng = np.random.RandomState(seed)
    shapes = _lubm_shapes(d, n_universities, rng)
    weights = np.array([s[1] for s in shapes], float)
    picks = rng.choice(len(shapes), burst, p=weights / weights.sum())
    t0 = time.perf_counter()
    reqs = {eng.submit(LUBM_SPARQL[q]):
            (q, parse_bgp(LUBM_SPARQL[q], d).patterns) for q in QUERIES}
    for i in picks:
        pats = shapes[i][2]()
        reqs[eng.submit(pats)] = (shapes[i][0], pats)
    results = {r.request_id: r for r in eng.drain()}
    log(f"[{tag}] {len(QUERIES)} LUBM queries + a burst of {burst}: "
        f"{eng.dispatches} dispatches for {eng.dispatched_queries} "
        f"dispatched queries (avg batch "
        f"{eng.dispatched_queries / max(eng.dispatches, 1):.1f}), "
        f"escalations={eng.escalations} fallbacks={eng.fallbacks}, "
        f"{time.perf_counter() - t0:.2f}s (compiles included)")
    for rid, (name, pats) in reqs.items():
        check_result(name if name in QUERIES else f"{name}#{rid}",
                     results[rid], triples, pats)
    if burst and eng.dispatched_queries <= eng.dispatches:
        raise RuntimeError("no dispatch served more than one request")


def lubm_caps():
    """Probe/row caps that hold LUBM's fan-outs (120 students per
    department, as examples/sparql_lubm.py sizes them); the default row
    budget holds every served query's rows."""
    from repro.core import Caps
    return Caps(probe_cap=128, row_cap=64)


def phase_kernels(store, d, triples: np.ndarray,
                  impl: str = "pallas") -> None:
    """Phase 4: the LUBM queries through execute_local with the Pallas
    kernels (compiled on the chip; `impl` lets a CPU test interpret
    them), equal to impl="jnp" and to the reference."""
    from repro.core import ExecConfig, compile_plan, execute_local, rows_set
    from repro.data.rdf_gen import LUBM_SPARQL
    from repro.serve import parse_bgp
    caps = lubm_caps()
    for q in QUERIES:
        pats = list(parse_bgp(LUBM_SPARQL[q], d).patterns)
        plan = compile_plan(store, pats, caps)
        t0 = time.perf_counter()
        got = execute_local(store, plan, cfg=ExecConfig(impl=impl))
        jax.block_until_ready(got.table)
        t1 = time.perf_counter()
        ref = execute_local(store, plan)
        rows_k = rows_set(got.table, got.valid, len(got.vars))
        rows_j = rows_set(ref.table, ref.valid, len(ref.vars))
        want = reference_rows(triples, pats, got.vars)
        ops = "/".join(st.kind for st in plan.steps)
        log(f"  {q:10s} ops={ops} rows={len(rows_k)} jnp={len(rows_j)} "
            f"ref={len(want)} overflow={int(got.overflow)} "
            f"first_call_s={t1 - t0:.2f}")
        if int(got.overflow) or int(ref.overflow):
            raise RuntimeError(f"{q}: truncated at {caps}")
        if rows_k != rows_j or rows_k != want:
            raise RuntimeError(f"{q}: impl={impl} rows differ from jnp "
                               f"or the reference")


def phase_durable(seed: int, n_universities: int = 2,
                  batch: int = 2048) -> None:
    """Phase 5: ingest LUBM-`n` as string triples in batches across at
    least one flush, reopen (recovery), and serve it."""
    from repro.data import lubm_like
    from repro.store import MutableTripleStore
    triples, d, _ = lubm_like(n_universities, seed=seed)
    terms = [(d.term(s), d.term(p), d.term(o)) for s, p, o in
             triples.tolist()]
    root = tempfile.mkdtemp(prefix="chip_smoke_store_")
    try:
        st = MutableTripleStore.create(root, overlay_limit=4 * batch)
        t0 = time.perf_counter()
        for i in range(0, len(terms), batch):
            st.ingest_terms(terms[i:i + batch])
        t1 = time.perf_counter()
        flushes = st.flush_count
        st.close()
        if flushes < 1:
            raise RuntimeError("ingest never flushed")
        st = MutableTripleStore.open(root)
        t2 = time.perf_counter()
        log(f"[durable] ingested {len(terms)} triples in "
            f"{-(-len(terms) // batch)} batches, {flushes} flush(es), "
            f"{t1 - t0:.2f}s; reopened in {t2 - t1:.2f}s: "
            f"triples={st.n_triples} terms={len(st.dictionary)}")
        # the reference reads the source triples in the RECOVERED
        # dictionary's ids: recovery must have kept every term and triple
        ids = [st.dictionary.lookup(t) for row in terms for t in row]
        if None in ids or st.n_triples != len(np.unique(triples, axis=0)):
            raise RuntimeError("recovery lost terms or triples")
        recovered = np.array(ids, np.int64).reshape(-1, 3)
        phase_serve(st, st.dictionary, recovered, n_universities,
                    burst=0, seed=seed, tag="durable")
        st.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)


def phase_sharded(n_universities: int, seed: int, chips: int = 4) -> None:
    """--chips 4: the store range-partitioned into one region per chip on
    a mesh, served with a2a routing, checked against the reference and
    one-device execute_local."""
    from jax.sharding import Mesh
    from repro.core import ExecConfig, build_store, execute_local, rows_set
    from repro.data.rdf_gen import LUBM_SPARQL
    from repro.serve import ServeEngine, parse_bgp
    triples, d, store = phase_load(n_universities, seed, num_shards=chips)
    one = build_store(triples, num_shards=1)
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    # LUBM-sized probe caps: each template compiles once instead of once
    # per escalation rung (a four-chip compile is the costly part here)
    eng = ServeEngine(store, d, mesh=mesh, cfg=ExecConfig(routing="a2a"),
                      caps=lubm_caps())
    texts = {q: LUBM_SPARQL[q] for q in QUERIES}
    t0 = time.perf_counter()
    rids = {eng.submit(text): q for q, text in texts.items()}
    results = {r.request_id: r for r in eng.drain()}
    log(f"[sharded] {len(texts)} LUBM queries over {chips} regions, "
        f"routing=a2a: {time.perf_counter() - t0:.2f}s (compiles included)")
    for rid, q in rids.items():
        pats = parse_bgp(texts[q], d).patterns
        res = results[rid]
        check_result(q, res, triples, pats)
        local = execute_local(one, list(pats), caps=lubm_caps())
        if int(local.overflow):
            raise RuntimeError(f"{q}: one-device execute_local truncated")
        want = rows_set(local.table, local.valid, len(local.vars))
        perm = [res.vars.index(v) for v in local.vars]
        got = set(tuple(r[i] for i in perm) for r in res.rows_set())
        if got != want:
            raise RuntimeError(f"{q}: sharded rows differ from one-device "
                               f"execute_local")
    log(f"[sharded] all {len(texts)} queries equal the reference and "
        f"one-device execute_local")
    shard_bytes = {s.device: s.data.nbytes
                   for s in store.keys_spo.addressable_shards}
    for dev in mesh.devices.ravel():
        stats = dev.memory_stats() or {}
        log(f"[sharded] device {dev.id}: bytes_in_use="
            f"{stats.get('bytes_in_use', 'n/a')} "
            f"spo_region_bytes={shard_bytes.get(dev, 0)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = phase_device(args.chips)
    from repro.common import enable_compile_cache
    log(f"[setup] compile cache: {enable_compile_cache()}")
    watch = CompileWatch()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(DEFAULT_UNIVERSITIES, args.seed)
    else:
        triples, d, store = phase_load(DEFAULT_UNIVERSITIES, args.seed)
        phase_serve(store, d, triples, DEFAULT_UNIVERSITIES, DEFAULT_BURST,
                    args.seed)
        log(f"[serve] {watch.line()}")
        log("[kernels] impl=pallas vs impl=jnp, execute_local")
        phase_kernels(store, d, triples)
        log(f"[kernels] {watch.line()}")
        phase_durable(args.seed)
    log(f"[done] wall_s={time.perf_counter() - t0:.2f} {watch.line()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
