"""MAPSIN join — map-side index nested-loop join (paper §4), local primitives.

Everything here operates on one shard's data with static shapes:
  * ``Bindings`` — a fixed-capacity multiset of solution mappings
    (MapReduce's unbounded lists -> capacity + validity mask + overflow
    counter; overflow is *surfaced*, never silent).
  * ``scan_pattern``    — the distributed-table-scan input phase (§4.1 step 1+2)
  * ``probe``           — the index GET: binary-search range + gather + filter
  * ``mapsin_step``     — Algorithm 1 (one cascading iteration)
  * ``multiway_step``   — Algorithms 2+3 (star joins, single row-GET)

The distributed versions in core/distributed.py wrap these in shard_map.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core.plan import (PatternPlan, make_plan, probe_ranges,
                             residual_values, row_range)
from repro.core.rdf import unpack3


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Bindings:
    """Fixed-capacity multiset of solution mappings Omega."""
    vars: tuple[str, ...]          # static aux
    table: jnp.ndarray             # (cap, n_vars) int32
    valid: jnp.ndarray             # (cap,) bool
    overflow: jnp.ndarray          # () int32 — dropped rows (capacity misses)

    def tree_flatten(self):
        return (self.table, self.valid, self.overflow), self.vars

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux, *children)

    @property
    def capacity(self) -> int:
        return self.table.shape[0]

    def count(self) -> jnp.ndarray:
        return jnp.sum(self.valid.astype(jnp.int32))

    @classmethod
    def empty(cls, vars: Sequence[str], cap: int) -> "Bindings":
        return cls(tuple(vars), jnp.zeros((cap, len(vars)), jnp.int32),
                   jnp.zeros((cap,), bool), jnp.zeros((), jnp.int32))


def compact(rows: jnp.ndarray, valid: jnp.ndarray, out_cap: int,
            buf: jnp.ndarray | None = None):
    """Pack valid rows (N, nv) to the front of a (out_cap, nv) buffer.

    Returns (table, valid_mask, n_dropped). When `buf` (a zeroed
    (out_cap, nv) array, e.g. a donated scratch Bindings table) is given,
    it supplies the padding slots — no fresh allocation.

    GATHER-formulated: the running count c = cumsum(valid) is
    non-decreasing, so the source row of output slot p (the (p+1)-th
    valid row) is ``searchsorted(c, p, side="right")`` — O(out_cap log N)
    rank-finds plus an out_cap-row gather. The former positional scatter
    of all N rows was the dominant cascade cost on CPU hosts (XLA
    serializes scatters); results are bit-identical.
    """
    if buf is None:
        buf = jnp.zeros((out_cap, rows.shape[1]), rows.dtype)
    if valid.shape[0] == 0:
        return buf, jnp.zeros((out_cap,), bool), jnp.zeros((), jnp.int32)
    c = jnp.cumsum(valid.astype(jnp.int32))                # running count
    total = c[-1]
    dropped = jnp.maximum(total - out_cap, 0)
    src = jnp.searchsorted(c, jnp.arange(out_cap, dtype=jnp.int32),
                           side="right")
    src = jnp.minimum(src, valid.shape[0] - 1)
    vmask = jnp.arange(out_cap) < jnp.minimum(total, out_cap)
    out = jnp.where(vmask[:, None], rows[src], buf)
    return out, vmask, dropped


# ---------------------------------------------------------------------------
# Index probes (HBase GET with predicate push-down)
# ---------------------------------------------------------------------------


def searchsorted(keys: jnp.ndarray, queries: jnp.ndarray,
                 impl: str = "jnp") -> jnp.ndarray:
    """'left' ranks of `queries` in the sorted `keys`: ``jnp.searchsorted``
    for impl="jnp", the Pallas kernel (kernels/searchsorted.py) compiled
    for impl="pallas" and interpreted for impl="pallas_interpret"."""
    if impl == "jnp":
        return jnp.searchsorted(keys, queries)
    from repro.kernels import ops
    return ops.searchsorted(keys, queries,
                            interpret=(impl == "pallas_interpret"))


def gather_range(keys: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                 cap: int, impl: str = "jnp"):
    """For each probe range, gather up to `cap` composite keys.

    keys: (M,) sorted int64 (INF padded). lo/hi: (B,).
    Returns (k (B, cap), valid (B, cap), n_missed (B,)).
    """
    m = keys.shape[0]
    start = searchsorted(keys, lo, impl)
    end = searchsorted(keys, hi, impl)
    idx = start[:, None] + jnp.arange(cap)[None]
    k = keys[jnp.minimum(idx, m - 1)]
    valid = idx < end[:, None]
    missed = jnp.maximum(end - start - cap, 0)
    return k, valid, missed


def apply_residual(k: jnp.ndarray, valid: jnp.ndarray,
                   flt_vals: jnp.ndarray, flt_mask: tuple[bool, bool, bool],
                   eq_positions=()) -> jnp.ndarray:
    """Server-side filter: keep entries whose unpacked positions match."""
    t = unpack3(k)  # 3 x (B, cap)
    for pos in range(3):
        if flt_mask[pos]:
            valid = valid & (t[pos] == flt_vals[:, pos][:, None])
    for a, b in eq_positions:
        valid = valid & (t[a] == t[b])
    return valid


def probe(plan: PatternPlan, keys: jnp.ndarray, table: jnp.ndarray,
          row_valid: jnp.ndarray, cap: int, impl: str = "jnp"):
    """The MAPSIN inner loop body: dynamic GET for each input mapping.

    Returns (matched keys (B, cap), match mask, missed counts (B,)).
    With impl="pallas"/"pallas_interpret" the whole GET — rank-find, range
    gather, residual filter, slot placement — runs as ONE fused kernel
    (kernels/probe_gather.py); the jnp path below is the validated
    reference (match keys differ only at masked slots: the kernel writes
    0 where the reference leaves clamped-gather garbage).
    """
    lo, hi = probe_ranges(plan, table)
    lo = jnp.where(row_valid, lo, 0)
    hi = jnp.where(row_valid, hi, 0)   # invalid rows probe an empty range
    flt, msk = residual_values(plan, table)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops
        return ops.probe_gather(keys, lo, hi, flt, cap=cap, flt_mask=msk,
                                eq_positions=plan.eq_positions,
                                interpret=(impl == "pallas_interpret"))
    k, valid, missed = gather_range(keys, lo, hi, cap, impl)
    valid = apply_residual(k, valid, flt, msk, plan.eq_positions)
    return k, valid, missed


def merge_bindings(bindings: Bindings, plan: PatternPlan, k: jnp.ndarray,
                   match: jnp.ndarray, missed: jnp.ndarray,
                   out_cap: int) -> Bindings:
    """Merge mu_n with compatible mappings (Alg. 1 lines 11-17).

    Instead of broadcasting the old table to (bcap, cap, n_vars) and
    compacting the full widened rows, only the ORIGIN index plus the <= 3
    newly bound columns are scattered; the surviving old columns are
    gathered once at the end — the intermediate shrinks from
    (bcap*cap, n_vars+new) to (bcap*cap, 1+new).
    """
    bcap, cap = match.shape
    t = unpack3(k)
    origin = jnp.broadcast_to(
        jnp.arange(bcap, dtype=jnp.int32)[:, None], (bcap, cap))
    cols = [origin] + [t[pos].astype(jnp.int32) for _, pos in plan.out_vars]
    rows = jnp.stack([c.reshape(-1) for c in cols], axis=1)
    valid = (match & bindings.valid[:, None]).reshape(-1)
    packed, vmask, dropped = compact(rows, valid, out_cap)
    table = bindings.table[packed[:, 0]]
    if plan.out_vars:
        table = jnp.concatenate([table, packed[:, 1:]], axis=1)
    table = jnp.where(vmask[:, None], table, 0)
    overflow = (bindings.overflow + dropped
                + jnp.sum(jnp.where(bindings.valid, missed, 0)).astype(jnp.int32))
    return Bindings(bindings.vars + plan.out_var_names, table, vmask, overflow)


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def scan_pattern(pattern, keys: jnp.ndarray, out_cap: int,
                 impl: str = "jnp", scratch: "Bindings | None" = None) -> Bindings:
    """First-pattern input phase: scan the (locally stored) index slice.

    Equivalent of the distributed HBase table scan that feeds the map phase.
    `scratch` (a zeroed Bindings of matching shape, typically donated by the
    jitted cascade in core/bgp.py) is consumed as the output buffers.
    """
    plan = make_plan(pattern, ())
    empty = jnp.zeros((1, 0), jnp.int32)
    lo, hi = probe_ranges(plan, empty)
    flt, msk = residual_values(plan, empty)
    within = (keys >= lo[0]) & (keys < hi[0])
    within = apply_residual(keys[None, :], within[None, :],
                            jnp.broadcast_to(flt, (1, 3)), msk,
                            plan.eq_positions)[0]
    t = unpack3(keys)
    cols = [t[pos][:, None] for _, pos in plan.out_vars]
    rows = (jnp.concatenate(cols, axis=-1) if cols
            else jnp.zeros((keys.shape[0], 0), jnp.int64)).astype(jnp.int32)
    buf = scratch.table if scratch is not None else None
    table, vmask, dropped = compact(rows, within, out_cap, buf=buf)
    overflow = dropped.astype(jnp.int32)
    if scratch is not None:
        vmask = vmask | scratch.valid          # zeros; consumes the buffer
        overflow = overflow + scratch.overflow
    return Bindings(plan.out_var_names, table, vmask, overflow)


def mapsin_step(bindings: Bindings, pattern, keys: jnp.ndarray,
                probe_cap: int, out_cap: int, impl: str = "jnp") -> Bindings:
    """One cascading MAPSIN iteration (Algorithm 1) on local data."""
    plan = make_plan(pattern, bindings.vars)
    k, match, missed = probe(plan, keys, bindings.table, bindings.valid,
                             probe_cap, impl)
    return merge_bindings(bindings, plan, k, match, missed, out_cap)


def multiway_step(bindings: Bindings, patterns: Sequence, keys: jnp.ndarray,
                  row_cap: int, out_cap: int, impl: str = "jnp") -> Bindings:
    """Optimized multiway star join (Algorithm 3): ONE row-GET per input
    mapping answers all patterns sharing the join variable on the primary
    position; per-pattern predicate filters are applied to the fetched row.
    """
    plans = [make_plan(p, bindings.vars) for p in patterns]
    p0 = plans[0]
    assert all(pl.index == p0.index and len(pl.prefix) >= 1 and
               pl.prefix[0] == p0.prefix[0] for pl in plans), \
        "multiway requires a shared primary-position join variable"
    lo, hi = row_range(p0, bindings.table)
    lo = jnp.where(bindings.valid, lo, 0)
    hi = jnp.where(bindings.valid, hi, 0)
    k, in_row, missed = gather_range(keys, lo, hi, row_cap, impl)

    out = bindings
    origin = jnp.arange(bindings.capacity, dtype=jnp.int32)[:, None]
    cur_origin = origin[:, 0]                     # (cap,) row -> probe index
    for plan in plans:
        flt, msk = residual_values(plan, bindings.table)
        # secondary/tertiary prefix components become residual filters on
        # the fetched row (they were part of the GET key in the 2-way case)
        extra_vals = jnp.zeros((bindings.capacity, 3), jnp.int64)
        extra_msk = [False, False, False]
        for pos, sc in enumerate(plan.prefix[1:], start=1):
            from repro.core.plan import _resolve
            extra_vals = extra_vals.at[:, pos].set(_resolve(sc, bindings.table))
            extra_msk[pos] = True
        match = apply_residual(k, in_row, flt, msk, plan.eq_positions)
        match = apply_residual(k, match, extra_vals, tuple(extra_msk))
        # expand current out rows against this pattern's matches
        km = k[cur_origin]                         # (out_cap, row_cap)
        mm = match[cur_origin] & out.valid[:, None]
        t = unpack3(km)
        old = jnp.broadcast_to(out.table[:, None, :],
                               (out.capacity, row_cap, len(out.vars)))
        new_cols = [t[pos][..., None] for _, pos in plan.out_vars]
        rows = jnp.concatenate([old] + new_cols, -1) if new_cols else old
        ori = jnp.broadcast_to(cur_origin[:, None], (out.capacity, row_cap))
        rows = jnp.concatenate([rows, ori[..., None]], -1)
        table, vmask, dropped = compact(
            rows.reshape(out.capacity * row_cap, -1).astype(jnp.int32),
            mm.reshape(-1), out_cap)
        cur_origin = table[:, -1]
        out = Bindings(out.vars + plan.out_var_names, table[:, :-1], vmask,
                       out.overflow + dropped)
    overflow = out.overflow + jnp.sum(
        jnp.where(bindings.valid, missed, 0)).astype(jnp.int32)
    return Bindings(out.vars, out.table, out.valid, overflow)
