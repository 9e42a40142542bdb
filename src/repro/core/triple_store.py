"""Sharded sorted triple store — the HBase analogue (DESIGN.md §2).

Two indexes mirror the paper's two-table schema:
  T_spo — composite keys sorted by (s, p, o)   [row key = subject]
  T_ops — composite keys sorted by (o, p, s)   [row key = object]

Each index is range-partitioned into `num_shards` equal slices by sampled
quantiles of the *full composite key* (region boundaries). A fat row (the
paper's `rdf:type` problem) therefore legally spans shards — probes that
cover it fan out to every intersecting shard, which is exactly the paper's
compound-rowkey fix generalized: no single machine ever owns a whole class.

Shards are padded to equal length with INF keys so every per-shard array is
statically shaped (TPU requirement).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import ceil_div
from repro.core.rdf import INF_KEY, pack3

SPO, OPS = 0, 1  # index ids (paper Table 3 chooses between them per pattern)

PLAN_CACHE_SIZE = 512  # default plan_cache bound (entries, not bytes)


class LRUCache(OrderedDict):
    """Dict with least-recently-used eviction — bounds the per-store
    plan/compile cache (and the serving layer's per-engine compile cache)
    so a many-tenant query stream can't grow host memory forever.

    Reads (`[]` / `get`) refresh recency; writes evict the coldest entry
    once `maxsize` is exceeded. Evicting a compiled cascade only costs a
    re-trace on the next miss — never correctness.
    """

    def __init__(self, maxsize: int = PLAN_CACHE_SIZE):
        super().__init__()
        if maxsize < 1:
            raise ValueError("LRUCache needs maxsize >= 1")
        self.maxsize = maxsize

    def __getitem__(self, key):
        val = super().__getitem__(key)
        self.move_to_end(key)
        return val

    def get(self, key, default=None):
        if key in self:
            return self[key]
        return default

    def __setitem__(self, key, val):
        super().__setitem__(key, val)
        self.move_to_end(key)
        while len(self) > self.maxsize:
            del self[next(iter(self))]    # coldest (front) entry


@dataclasses.dataclass
class TripleStore:
    # (num_shards, shard_cap) int64, sorted ascending within & across shards
    keys_spo: jnp.ndarray
    keys_ops: jnp.ndarray
    # (num_shards + 1,) int64 region boundaries (splitters[0] = -1)
    splits_spo: jnp.ndarray
    splits_ops: jnp.ndarray
    counts_spo: jnp.ndarray  # (num_shards,) valid entries per shard
    counts_ops: jnp.ndarray
    n_triples: int
    # monotonically increasing mutation counter (DESIGN.md §9): 0 for a
    # build-once store, bumped by bump_version() on EVERY applied mutation
    # batch (ingest / flush / recovery replay). It is part of layout_key,
    # so every compile/plan/stat cache keyed on the store misses after a
    # mutation instead of serving rows from a pre-ingest world.
    store_version: int = 0
    # host-side memo: flattened keys, measured cardinalities, ordered step
    # plans and compiled cascades keyed by (patterns, cfg) — keeps repeated
    # query execution off the eager-dispatch path (core/bgp.py). LRU-bounded:
    # under a many-tenant query stream the per-(patterns, cfg) entries would
    # otherwise accumulate forever; hot entries stay resident, cold ones
    # re-trace on their next use.
    plan_cache: LRUCache = dataclasses.field(
        default_factory=LRUCache, repr=False, compare=False)

    @property
    def num_shards(self) -> int:
        return self.keys_spo.shape[0]

    @property
    def shard_cap(self) -> int:
        return self.keys_spo.shape[1]

    def keys(self, index: int) -> jnp.ndarray:
        return self.keys_spo if index == SPO else self.keys_ops

    def flat_keys(self, index: int) -> jnp.ndarray:
        key = ("flat_keys", index)
        if key not in self.plan_cache:
            self.plan_cache[key] = self.keys(index).reshape(-1)
        return self.plan_cache[key]

    def shard_onto(self, mesh, axis: str) -> None:
        """Place each index's region rows on the devices of `mesh` along
        `axis` (region k on the k-th device), so a sharded executor reads
        its regions where they sit instead of copying them out of one
        device on every call. A no-op when they are placed already; the
        cached flat views are dropped (they were views of the old
        arrays)."""
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = NamedSharding(mesh, PartitionSpec(axis, None))
        if getattr(self.keys_spo, "sharding", None) == sharding:
            return
        self.keys_spo = jax.device_put(self.keys_spo, sharding)
        self.keys_ops = jax.device_put(self.keys_ops, sharding)
        for index in (SPO, OPS):
            self.plan_cache.pop(("flat_keys", index), None)

    def splits(self, index: int) -> jnp.ndarray:
        return self.splits_spo if index == SPO else self.splits_ops

    @property
    def layout_key(self) -> tuple:
        """Hashable shard-layout identity: ``store_version`` + shard shape
        + the actual region boundaries of both indexes. A compiled cascade
        bakes the splits in as constants — and a compiled PLAN bakes in
        measured statistics — so any compile cache keyed on the store MUST
        include this: rebuilding, resharding, or MUTATING the store (live
        ingest bumps store_version even when the boundaries happen to
        survive) changes the key and can never reuse a stale compilation
        against post-ingest data."""
        ck = ("layout_key",)
        if ck not in self.plan_cache:
            self.plan_cache[ck] = (
                self.store_version,
                self.num_shards, self.shard_cap, self.n_triples,
                tuple(int(x) for x in np.asarray(self.splits_spo)),
                tuple(int(x) for x in np.asarray(self.splits_ops)))
        return self.plan_cache[ck]

    def bump_version(self) -> int:
        """Mutation barrier (DESIGN.md §9): advance ``store_version`` and
        drop EVERY memoized artifact in ``plan_cache`` — flattened key
        views, host key copies, ``relation_stats``/``pattern_cardinality``
        statistics, compiled plans with embedded measured capacities, and
        compiled cascades. Anything derived from pre-mutation key values
        is stale after an ingest: stale STATISTICS would only mis-price
        operators (results stay exact — caps truncation is surfaced and
        escalated, never silent), but a compiled sharded cascade bakes
        region splits in as constants and a cached plan bakes in measured
        a2a capacities, so wholesale invalidation is the only state a
        mutation can leave behind that is correct by construction."""
        self.store_version += 1
        self.plan_cache.clear()
        return self.store_version

    def storage_bytes(self) -> int:
        return int(self.keys_spo.size + self.keys_ops.size) * 8


def range_intersects_region(lo, hi, excl_lo, incl_hi):
    """Does probe range [lo, hi) intersect region (excl_lo, incl_hi]?

    Exact, not heuristic, because store keys are unique and globally
    sorted: the range misses the region iff lo > incl_hi or
    hi <= excl_lo + 1. The single source of truth for both the routed
    dist_probe mask (core/distributed.py) and the measured fan-out
    accounting (core/bgp.py). Works elementwise on numpy or jnp arrays.
    """
    return (lo <= incl_hi) & (hi > excl_lo + 1)


def _shard_sorted(keys: np.ndarray, num_shards: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Split a sorted key array into equal shards; return (padded, splits, counts)."""
    n = len(keys)
    cap = max(ceil_div(n, num_shards), 1)
    padded = np.full((num_shards, cap), INF_KEY, np.int64)
    splits = np.empty(num_shards + 1, np.int64)
    counts = np.zeros(num_shards, np.int64)
    splits[0] = np.int64(-1)
    for k in range(num_shards):
        lo, hi = k * cap, min((k + 1) * cap, n)
        cnt = max(hi - lo, 0)
        if cnt > 0:
            padded[k, :cnt] = keys[lo:hi]
        counts[k] = cnt
        splits[k + 1] = keys[hi - 1] if cnt > 0 else splits[k]
    splits[num_shards] = INF_KEY
    return padded, splits, counts


def build_store(triples: np.ndarray, num_shards: int = 1) -> TripleStore:
    """triples: (N, 3) int32. Bulk load (the paper's Table 4 operation)."""
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
    k_spo = np.sort(pack3(s, p, o))
    k_ops = np.sort(pack3(o, p, s))
    if len(k_spo) and k_spo[-1] == INF_KEY:
        # (MAX_ID, MAX_ID, MAX_ID) packs to the INF_KEY padding sentinel:
        # it would be indistinguishable from padding and unfindable (every
        # probe range's exclusive hi saturates at INF_KEY). The Dictionary
        # reserves id MAX_ID so encoded data can never hit this.
        raise ValueError("triple (MAX_ID, MAX_ID, MAX_ID) packs to the "
                         "INF_KEY sentinel and cannot be stored")
    # dedup (RDF set semantics)
    k_spo = np.unique(k_spo)
    k_ops = np.unique(k_ops)
    spo, sp_splits, sp_counts = _shard_sorted(k_spo, num_shards)
    ops, op_splits, op_counts = _shard_sorted(k_ops, num_shards)
    return TripleStore(
        keys_spo=jnp.asarray(spo), keys_ops=jnp.asarray(ops),
        splits_spo=jnp.asarray(sp_splits), splits_ops=jnp.asarray(op_splits),
        counts_spo=jnp.asarray(sp_counts), counts_ops=jnp.asarray(op_counts),
        n_triples=int(len(k_spo)),
    )
