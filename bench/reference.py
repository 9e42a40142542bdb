"""The plain reference: answers a basic graph pattern by numpy joins over
the raw (N, 3) id triples that the benchmark generated.

A copy of ``chip_smoke.reference_rows``: independent of the program's
store, planner, kernels and dictionary. A pattern is a triple of terms,
where a ``str`` is a variable and an ``int`` a constant id.
"""
from __future__ import annotations

import numpy as np


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


class Reference:
    """Answers BGPs over one triple array. Each pattern's relation is
    selected with numpy masks and kept, since traffic repeats patterns
    (``?x rdf:type <Student>`` rides every instance of a template)."""

    def __init__(self, triples: np.ndarray):
        self.triples = np.asarray(triples)
        self._relations: dict[tuple, tuple] = {}

    def relation(self, pattern) -> tuple[tuple, np.ndarray]:
        """(variables, distinct (n, k) int64 bindings) of one pattern."""
        pattern = tuple(pattern)
        hit = self._relations.get(pattern)
        if hit is not None:
            return hit
        triples = self.triples
        mask = np.ones(len(triples), bool)
        cols: dict[str, int] = {}
        for pos, term in enumerate(pattern):
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
        hit = self._relations[pattern] = (vars_, data[first])
        return hit

    def rows(self, patterns, var_order) -> set[tuple[int, ...]]:
        """Distinct solutions of the BGP in `var_order`: relations joined
        smallest-first, preferring ones that share a variable with what is
        already joined."""
        rels = sorted((self.relation(p) for p in patterns),
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
