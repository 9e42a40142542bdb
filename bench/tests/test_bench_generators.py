"""The benchmark's data generator follows LUBM's UBA profile and the
univ-bench entailments, deterministically per seed; the traffic's text
names the patterns the reference answers."""
import json
import os

import numpy as np
import pytest

import bench_cpu
from bench.gen.lubm import RDF_TYPE, UB, generate
from bench.traffic import Traffic, load, resolve_term


def _config(universities=1, **profile):
    with open(os.path.join(bench_cpu.ROOT, "bench", "configs",
                           "lubm50.json")) as f:
        cfg = json.load(f)
    cfg["universities"] = universities
    cfg["profile"].update(profile)
    return cfg


@pytest.fixture(scope="module")
def lubm1():
    triples, terms = generate(_config(), 0)
    ids = {t: i for i, t in enumerate(terms)}
    return triples, terms, ids


def _count(triples, ids, s=None, p=None, o=None):
    m = np.ones(len(triples), bool)
    for col, term in ((0, s), (1, p), (2, o)):
        if term is not None:
            m &= triples[:, col] == ids[term]
    return m


def test_deterministic_per_seed():
    cfg = _config(departments=[2, 3])
    a, ta = generate(cfg, 2**31 + 11)
    b, tb = generate(cfg, 2**31 + 11)
    c, _ = generate(cfg, 3)
    assert np.array_equal(a, b) and ta == tb
    assert not np.array_equal(a, c)


def test_university0_is_the_same_at_every_scale():
    cfg = _config(departments=[2, 3])
    one, t1 = generate(cfg, 0)
    two, t2 = generate(dict(cfg, universities=2), 0)
    as_text = lambda tr, t: {(t[s], t[p], t[o]) for s, p, o in tr.tolist()
                             if "University0.edu/" in t[s]}
    assert as_text(one, t1) == as_text(two, t2)


def test_terms_distinct_and_triples_distinct(lubm1):
    triples, terms, _ = lubm1
    assert len(set(terms)) == len(terms)
    key = (triples[:, 0].astype(np.int64) << 42) | (
        triples[:, 1].astype(np.int64) << 21) | triples[:, 2]
    assert len(np.unique(key)) == len(triples)


def test_counts_within_the_uba_profile(lubm1):
    triples, terms, ids = lubm1
    prof = _config()["profile"]
    typ = RDF_TYPE
    depts = [terms[i] for i in triples[_count(triples, ids, p=typ,
                                               o=UB + "Department"), 0]]
    lo, hi = prof["departments"]
    assert lo <= len(depts) <= hi
    works, member = ids[UB + "worksFor"], ids[UB + "memberOf"]
    for dept in depts:
        d = ids[dept]
        fac = set(triples[(triples[:, 1] == works) & (triples[:, 2] == d), 0])
        ranks = {}
        for r in ("FullProfessor", "AssociateProfessor",
                  "AssistantProfessor", "Lecturer"):
            n = int(np.isin(triples[_count(triples, ids, p=typ,
                                           o=UB + r), 0],
                            list(fac)).sum())
            lo, hi = prof[r.lower()]
            assert lo <= n <= hi, (dept, r, n)
            ranks[r] = n
        n_fac = sum(ranks.values())
        assert n_fac == len(fac)
        members = triples[(triples[:, 1] == member) & (triples[:, 2] == d), 0]
        ug = int(np.isin(members, triples[_count(
            triples, ids, p=typ, o=UB + "UndergraduateStudent"), 0]).sum())
        gr = int(np.isin(members, triples[_count(
            triples, ids, p=typ, o=UB + "GraduateStudent"), 0]).sum())
        assert 8 * n_fac <= ug <= 14 * n_fac
        assert 3 * n_fac <= gr <= 4 * n_fac


def test_entailments_materialized(lubm1):
    triples, terms, ids = lubm1
    typ = RDF_TYPE
    of_type = lambda c: set(triples[_count(triples, ids, p=typ,
                                           o=UB + c), 0].tolist())
    profs = of_type("FullProfessor") | of_type("AssociateProfessor") | \
        of_type("AssistantProfessor")
    assert profs == of_type("Professor")
    assert of_type("Professor") | of_type("Lecturer") == of_type("Faculty")
    takers = set(triples[_count(triples, ids, p=UB + "takesCourse"),
                         0].tolist())
    assert takers == of_type("Student")
    assert of_type("UndergraduateStudent") | of_type("GraduateStudent") \
        == of_type("Student")
    heads = set(triples[_count(triples, ids, p=UB + "headOf"), 0].tolist())
    assert heads == of_type("Chair") and heads <= of_type("Professor")
    assert of_type("Person") >= of_type("Faculty") | of_type("Student")
    # subproperties and inverses
    n_deg = int(_count(triples, ids, p=UB + "degreeFrom").sum())
    degrees = np.concatenate([triples[_count(triples, ids, p=UB + p)][:, ::2]
                              for p in ("undergraduateDegreeFrom",
                                        "mastersDegreeFrom",
                                        "doctoralDegreeFrom")])
    assert n_deg == len(np.unique(degrees, axis=0))
    assert int(_count(triples, ids, p=UB + "hasAlumnus").sum()) == n_deg
    assert int(_count(triples, ids, p=UB + "member").sum()) == int(
        _count(triples, ids, p=UB + "memberOf").sum())
    # research groups are sub-organizations of their university too
    univ = "http://www.University0.edu"
    groups = of_type("ResearchGroup")
    sub_univ = set(triples[_count(triples, ids, p=UB + "subOrganizationOf",
                                  o=univ), 0].tolist())
    assert groups <= sub_univ


def test_query_stream_and_text():
    """Each query `repeat` times in a row, in the file's order, the same
    for every seed; the SPARQL text names the reference's patterns."""
    from repro.core.rdf import Dictionary
    from repro.serve import parse_bgp
    spec = load(os.path.join(bench_cpu.ROOT, "bench", "traffic",
                             "lubm-query-test.json"))
    t = Traffic(spec)
    nq, rep = len(spec["queries"]), spec["repeat"]
    stream = [t.request(k).query for k in range(3 * nq * rep)]
    assert stream == [(k // rep) % nq for k in range(3 * nq * rep)]
    triples, terms = generate(_config(departments=[1, 1]), 0)
    d = Dictionary()
    for term in terms:
        d.id(term)
    term_id = {term: i for i, term in enumerate(terms)}
    for i, text in enumerate(t.texts):
        parsed = parse_bgp(text, d)
        assert [tuple(p.terms) for p in parsed.patterns] == \
            t.patterns(i, term_id)
    assert resolve_term("a", spec["prefixes"]) == RDF_TYPE
    assert resolve_term('"t1"', {}) == "t1"
    assert resolve_term("ub:name", spec["prefixes"]) == UB + "name"
