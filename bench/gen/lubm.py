"""LUBM data by the rules of its generator, UBA (Guo, Pan and Heflin, J.
Web Semantics 3(2-3), 2005, and UBA's data profile), with the
entailments of the univ-bench ontology that LUBM's answers are
defined on materialized into the data.

Every count below is drawn uniformly from a range of the configuration's
``profile`` (UBA's own ranges): departments per university, faculty of
each rank per department, students per faculty member, courses per
faculty member, publications per rank, research groups, advisors,
teaching and research assistants. IRIs and literals are UBA's
(``http://www.Department0.University0.edu/FullProfessor0``, name
``FullProfessor0``, email ``FullProfessor0@Department0.University0.edu``,
telephone ``xxx-xxx-xxxx``). Each university draws from a stream of its
own (``data_seed``, university index), so University0 is the same at
every scale. The draws are numpy's, not UBA's Java ones: the counts have
UBA's distribution, not its exact values.

The closure: subclass and subproperty hierarchies, the classes defined
by a property (``Student``: takes a course, ``Chair``: heads a
department, ``TeachingAssistant``: assists a course), the inverses
``hasAlumnus``/``degreeFrom`` and ``member``/``memberOf``, and the
transitive ``subOrganizationOf``.
"""
from __future__ import annotations

import numpy as np

RDF_TYPE = "http://www.w3.org/1999/02/22-rdf-syntax-ns#type"
UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
TELEPHONE = "xxx-xxx-xxxx"
RANKS = ("FullProfessor", "AssociateProfessor", "AssistantProfessor",
         "Lecturer")

# the univ-bench ontology: every superclass of a class (transitively)
SUPERCLASSES = {
    "FullProfessor": ("Professor", "Faculty", "Employee", "Person"),
    "AssociateProfessor": ("Professor", "Faculty", "Employee", "Person"),
    "AssistantProfessor": ("Professor", "Faculty", "Employee", "Person"),
    "Chair": ("Professor", "Faculty", "Employee", "Person"),
    "Lecturer": ("Faculty", "Employee", "Person"),
    "UndergraduateStudent": ("Student", "Person"),
    "GraduateStudent": ("Person",),
    "Student": ("Person",),
    "TeachingAssistant": ("Person",),
    "ResearchAssistant": ("Person",),
    "GraduateCourse": ("Course", "Work"),
    "Course": ("Work",),
    "University": ("Organization",),
    "Department": ("Organization",),
    "ResearchGroup": ("Organization",),
}
# every superproperty of a property (transitively)
SUPERPROPERTIES = {
    "headOf": ("worksFor", "memberOf"),
    "worksFor": ("memberOf",),
    "undergraduateDegreeFrom": ("degreeFrom",),
    "mastersDegreeFrom": ("degreeFrom",),
    "doctoralDegreeFrom": ("degreeFrom",),
}
INVERSES = {"degreeFrom": "hasAlumnus", "memberOf": "member"}
# a class whose members are the subjects of a property
DEFINED_BY = {"takesCourse": "Student", "headOf": "Chair",
              "teachingAssistantOf": "TeachingAssistant"}
TRANSITIVE = ("subOrganizationOf",)


class Terms:
    """Term ids in first-minted order. Shared terms (vocabulary, names,
    universities) go through a dict; entity IRIs and emails, which are
    unique, are minted in blocks."""

    def __init__(self):
        self.terms: list[str] = []
        self.ids: dict[str, int] = {}

    def id(self, term: str) -> int:
        i = self.ids.get(term)
        if i is None:
            i = self.ids[term] = len(self.terms)
            self.terms.append(term)
        return i

    def ub(self, local: str) -> int:
        return self.id(UB + local)

    def block(self, strings: list[str]) -> np.ndarray:
        start = len(self.terms)
        self.terms.extend(strings)
        return np.arange(start, start + len(strings), dtype=np.int64)

    def names(self, prefix: str, n: int) -> np.ndarray:
        return np.array([self.id(f"{prefix}{i}") for i in range(n)],
                        np.int64)


def _draw(rng, rng_range, size=None):
    lo, hi = rng_range
    return rng.integers(lo, hi + 1, size=size)


def _pick(rng, n_rows: int, counts: np.ndarray, n_items: int):
    """For row i, counts[i] distinct items of range(n_items): (row, item)
    pairs."""
    order = np.argsort(rng.random((n_rows, n_items)), axis=1)
    take = np.arange(n_items)[None, :] < counts[:, None]
    rows = np.repeat(np.arange(n_rows), counts)
    return rows, order[take]


def _department(T: Terms, out: list, rng, prof: dict, u: int, d: int,
                univ: np.ndarray) -> None:
    def add(s, p, o):
        s, o = np.broadcast_arrays(np.asarray(s, np.int64),
                                   np.asarray(o, np.int64))
        out.append(np.stack([s.ravel(), np.full(s.size, p, np.int64),
                             o.ravel()], axis=1))

    typ, name, email = T.id(RDF_TYPE), T.ub("name"), T.ub("emailAddress")
    tel = T.ub("telephone")
    host = f"Department{d}.University{u}.edu"
    dept = T.block([f"http://www.{host}"])[0]
    add(dept, typ, T.ub("Department"))
    add(dept, name, T.id(f"Department{d}"))
    add(dept, T.ub("subOrganizationOf"), univ[u])

    def people(kind: str, n: int) -> np.ndarray:
        ids = T.block([f"http://www.{host}/{kind}{i}" for i in range(n)])
        mails = T.block([f"{kind}{i}@{host}" for i in range(n)])
        add(ids, typ, T.ub(kind))
        add(ids, name, T.names(kind, n))
        add(ids, email, mails)
        add(ids, tel, T.id(TELEPHONE))
        return ids

    # faculty, by rank
    counts = [int(_draw(rng, prof[r.lower()])) for r in RANKS]
    fac = np.concatenate([people(r, n) for r, n in zip(RANKS, counts)])
    rank = np.repeat(np.arange(4), counts)
    n_fac = len(fac)
    n_prof = n_fac - counts[3]           # professors come first
    add(fac, T.ub("worksFor"), dept)
    add(fac[0], T.ub("headOf"), dept)    # a full professor heads it
    for deg in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                "doctoralDegreeFrom"):
        add(fac, T.ub(deg), _univ_ids(T, rng, prof, n_fac))
    interests = _draw(rng, (0, prof["research_interests"] - 1), n_prof)
    add(fac[:n_prof], T.ub("researchInterest"),
        np.array([T.id(f"Research{k}") for k in interests], np.int64))

    # courses: each faculty member teaches its own
    courses = {}
    for kind, key in (("Course", "courses_per_faculty"),
                      ("GraduateCourse", "graduate_courses_per_faculty")):
        per = _draw(rng, prof[key], n_fac)
        ids = T.block([f"http://www.{host}/{kind}{i}"
                       for i in range(int(per.sum()))])
        add(ids, typ, T.ub(kind))
        add(ids, name, T.names(kind, len(ids)))
        add(np.repeat(fac, per), T.ub("teacherOf"), ids)
        courses[kind] = ids

    # publications of the faculty
    pubs = []
    for r in range(4):
        for f in fac[rank == r]:
            n = int(_draw(rng, prof["publications"][RANKS[r].lower()]))
            local = T.terms[f].rsplit("/", 1)[1]
            ids = T.block([f"http://www.{host}/{local}/Publication{j}"
                           for j in range(n)])
            add(ids, typ, T.ub("Publication"))
            add(ids, name, T.names("Publication", n))
            add(ids, T.ub("publicationAuthor"), f)
            pubs.append(ids)
    pubs = np.concatenate(pubs)

    # students: per faculty member, in UBA's ratios
    n_ug = int(_draw(rng, [k * n_fac for k in
                           prof["undergraduates_per_faculty"]]))
    n_gr = int(_draw(rng, [k * n_fac for k in
                           prof["graduates_per_faculty"]]))
    ug = people("UndergraduateStudent", n_ug)
    gr = people("GraduateStudent", n_gr)
    member, takes = T.ub("memberOf"), T.ub("takesCourse")
    advisor = T.ub("advisor")
    add(ug, member, dept)
    add(gr, member, dept)
    cs, gcs = courses["Course"], courses["GraduateCourse"]
    rows, items = _pick(rng, n_ug, _draw(
        rng, prof["courses_per_undergraduate"], n_ug), len(cs))
    add(ug[rows], takes, cs[items])
    rows, items = _pick(rng, n_gr, _draw(
        rng, prof["courses_per_graduate"], n_gr), len(gcs))
    add(gr[rows], takes, gcs[items])
    advised = rng.random(n_ug) < prof["undergraduates_advised"]
    add(ug[advised], advisor,
        fac[rng.integers(0, n_prof, int(advised.sum()))])
    add(gr, advisor, fac[rng.integers(0, n_prof, n_gr)])
    add(gr, T.ub("undergraduateDegreeFrom"), _univ_ids(T, rng, prof, n_gr))
    # teaching assistants (each of one course) and research assistants
    lo, hi = prof["teaching_assistants_per_graduate"]
    n_ta = int(rng.integers(int(n_gr * lo), int(n_gr * hi) + 1))
    who = rng.permutation(n_gr)
    ta = gr[who[:n_ta]]
    add(ta, typ, T.ub("TeachingAssistant"))
    add(ta, T.ub("teachingAssistantOf"), cs[rng.permutation(len(cs))[:n_ta]])
    lo, hi = prof["research_assistants_per_graduate"]
    n_ra = int(rng.integers(int(n_gr * lo), int(n_gr * hi) + 1))
    add(gr[rng.permutation(n_gr)[:n_ra]], typ, T.ub("ResearchAssistant"))
    # graduate students co-author publications of the department
    rows, items = _pick(rng, n_gr, _draw(
        rng, prof["publications"]["graduate"], n_gr), len(pubs))
    add(pubs[items], T.ub("publicationAuthor"), gr[rows])

    # research groups
    n_rg = int(_draw(rng, prof["research_groups"]))
    rg = T.block([f"http://www.{host}/ResearchGroup{i}" for i in range(n_rg)])
    add(rg, typ, T.ub("ResearchGroup"))
    add(rg, T.ub("subOrganizationOf"), dept)


def _univ_ids(T: Terms, rng, prof: dict, n: int) -> np.ndarray:
    """Universities a degree is from: any of UBA's degree universities,
    generated or not."""
    k = rng.integers(0, prof["degree_universities"], n)
    return np.array([T.id(f"http://www.University{i}.edu") for i in k],
                    np.int64)


def closure(triples: np.ndarray, T: Terms) -> np.ndarray:
    """The triples with the ontology's entailments added, distinct."""
    typ = T.id(RDF_TYPE)
    s, p, o = triples[:, 0], triples[:, 1], triples[:, 2]
    parts = [triples]

    def with_p(prop: int, mask) -> np.ndarray:
        return np.stack([s[mask], np.full(int(mask.sum()), prop, np.int64),
                         o[mask]], axis=1)

    for prop, supers in SUPERPROPERTIES.items():
        m = p == T.ub(prop)
        parts += [with_p(T.ub(sp), m) for sp in supers]
    t = np.concatenate(parts)
    s, p, o = t[:, 0], t[:, 1], t[:, 2]
    parts = [t]
    for prop, inv in INVERSES.items():
        m = p == T.ub(prop)
        parts.append(np.stack([o[m], np.full(int(m.sum()), T.ub(inv),
                                             np.int64), s[m]], axis=1))
    for prop, cls in DEFINED_BY.items():
        subj = np.unique(s[p == T.ub(prop)])
        parts.append(np.stack([subj, np.full(len(subj), typ, np.int64),
                               np.full(len(subj), T.ub(cls), np.int64)],
                              axis=1))
    for prop in TRANSITIVE:              # chains are two links long
        m = p == T.ub(prop)
        a, b = s[m], o[m]
        order = np.argsort(a)
        a_s, b_s = a[order], b[order]
        lo = np.searchsorted(a_s, b, "left")
        hi = np.searchsorted(a_s, b, "right")
        has = hi > lo
        # in UBA data each organization has one parent
        parts.append(np.stack([a[has], np.full(int(has.sum()), T.ub(prop),
                                               np.int64), b_s[lo[has]]],
                              axis=1))
    t = np.concatenate(parts)
    types = t[t[:, 1] == typ]
    parts = [t]
    for cls, supers in SUPERCLASSES.items():
        subj = types[types[:, 2] == T.ub(cls), 0]
        for sc in supers:
            parts.append(np.stack([subj, np.full(len(subj), typ, np.int64),
                                   np.full(len(subj), T.ub(sc), np.int64)],
                                  axis=1))
    t = np.concatenate(parts)
    key = (t[:, 0] << 42) | (t[:, 1] << 21) | t[:, 2]
    _, first = np.unique(key, return_index=True)
    return t[np.sort(first)]


def generate(config: dict, seed: int):
    """(triples (N, 3) int32, terms) of LUBM(universities, seed)."""
    prof = config["profile"]
    T = Terms()
    T.id(RDF_TYPE)
    n_univ = int(config["universities"])
    univ = np.array([T.id(f"http://www.University{u}.edu")
                     for u in range(n_univ)], np.int64)
    out: list[np.ndarray] = []
    for u in range(n_univ):
        rng = np.random.default_rng(np.random.SeedSequence([seed, u]))
        out.append(np.array([[univ[u], T.id(RDF_TYPE), T.ub("University")],
                             [univ[u], T.ub("name"),
                              T.id(f"University{u}")]], np.int64))
        for d in range(int(_draw(rng, prof["departments"]))):
            _department(T, out, rng, prof, u, d, univ)
    triples = closure(np.concatenate(out), T)
    return triples.astype(np.int32), T.terms
