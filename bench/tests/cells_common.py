"""Checks of a cell at a tiny scale on the CPU: every query served from
SPARQL text equals the reference, the control and each fault a one-chip
serving cell can have come out not correct."""
from __future__ import annotations

import io

import bench_cpu
from bench import faults


def queries_equal_reference(workload: str, root: str, copies: int = 3):
    """Submit `copies` of each query through the engine, as text, and
    hold every answer to the reference."""
    from bench.harness import prepare
    from bench.reference import Reference
    cell = prepare(root, workload, 17, bench_cpu.cpu_device,
                   overrides=bench_cpu.tiny_config(),
                   traffic_overrides=bench_cpu.TINY_TRAFFIC,
                   err=io.StringIO())
    sent = {}
    for i, text in enumerate(cell.traffic.texts):
        for _ in range(copies):
            sent[cell.eng.submit(text)] = i
    results = {res.request_id: res for res in cell.eng.drain()}
    ref = Reference(cell.triples)
    term_id = {t: i for i, t in enumerate(cell.terms)}
    rows = {}
    for rid, i in sent.items():
        res = results[rid]
        want = ref.rows(cell.traffic.patterns(i, term_id), res.vars)
        name = cell.traffic.queries[i]["name"]
        assert res.overflow == 0, name
        assert res.rows_set() == want and len(res.rows) == len(want), name
        rows[name] = len(want)
    assert all(rows.values()), rows           # every query answered rows
    return cell, rows


def run_is_correct(workload: str, root: str, **kw) -> dict:
    res = bench_cpu.run_tiny(workload, root=root, **kw)
    assert res["correct"], res["check"]
    assert res["check"]["wrong"]["value"] == 0
    assert res["attempted"] > 0
    return res


def control_fails(workload: str, root: str) -> dict:
    res = bench_cpu.run_tiny(workload, root=root, control=True)
    assert not res["correct"]
    assert res["check"]["wrong"]["value"] > 0
    return res


def altered_answer_fails(workload: str, root: str) -> None:
    """An answer altered where it is produced."""
    undo = faults.plant_altered_answer()
    try:
        res = bench_cpu.run_tiny(workload, root=root)
    finally:
        undo()
    assert not res["correct"] and res["check"]["wrong"]["value"] > 0


def half_batch_dropped_fails(workload: str, root: str,
                             monkeypatch) -> None:
    """Half of each dispatched batch left out: those answers never come."""
    from bench import loops
    monkeypatch.setattr(loops, "GRACE_S", 0.5)
    undo = faults.plant_half_batch_dropped()
    try:
        res = bench_cpu.run_tiny(workload, root=root)
    finally:
        undo()
    assert not res["correct"] and res["check"]["missing"]["value"] > 0
    assert res["failed"] > 0
