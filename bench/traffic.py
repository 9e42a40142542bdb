"""The general traffic generator: reads a traffic file
(``bench/traffic/<mix>.json``) and produces the stream of requests.

A traffic file holds:

* ``clients``: closed-loop clients; each sends its next request when its
  previous one is answered;
* ``repeat``: how many times in a row each query is sent;
* ``prefixes`` and ``queries``: each query has a ``name``, the
  ``select``ed variables and its ``where`` patterns, three SPARQL terms
  each;
* ``warmup_rounds``: how many times set-up serves each query at each
  batch size the clients can fill (``harness.warm_up``);
* ``check_sample``: how many answers of the window the reference checks.

The stream is the queries in their order, each ``repeat`` times, over
and over, the same for every seed; ``--seed`` draws the sample of answers
that the check holds to the reference.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

# stream ids folded into a seed
SAMPLE = 0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Request:
    query: int                  # index into the traffic's queries
    text: str                   # SPARQL text as sent


def resolve_term(token: str, prefixes: dict) -> str:
    """The term a SPARQL token names: ``<iri>``, ``"literal"``,
    ``pfx:local`` or ``a``; variables (``?v``) are returned as they are."""
    if token.startswith("?"):
        return token
    if token == "a":
        return prefixes["rdf"] + "type"
    if token[0] in "<\"":
        return token[1:-1]
    pfx, local = token.split(":", 1)
    return prefixes[pfx] + local


class Traffic:
    def __init__(self, spec: dict):
        self.spec = spec
        self.prefixes = spec.get("prefixes", {})
        self.queries = spec["queries"]
        self.repeat = int(spec.get("repeat", 1))
        head = "".join(f"PREFIX {p}: <{iri}>\n"
                       for p, iri in self.prefixes.items())
        self.texts = []
        for q in self.queries:
            body = "".join(f"  {s} {p} {o} .\n" for s, p, o in q["where"])
            self.texts.append(f"{head}SELECT {' '.join(q['select'])} "
                              f"WHERE {{\n{body}}}")

    def request(self, k: int) -> Request:
        """Request `k` of the stream."""
        i = (k // self.repeat) % len(self.queries)
        return Request(i, self.texts[i])

    def patterns(self, query: int, term_id: dict) -> list[tuple]:
        """The query's patterns in the benchmark's own term ids, for the
        reference: variables stay strings, constants become ints."""
        pats = []
        for triple in self.queries[query]["where"]:
            pat = []
            for tok in triple:
                term = resolve_term(tok, self.prefixes)
                pat.append(term if term.startswith("?") else term_id[term])
            pats.append(tuple(pat))
        return pats
