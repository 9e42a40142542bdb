"""The engine's spans on the device clock (``bench/spans.py``): the
clock map, the profile's op scopes, the idle split by program span, and
one traced window at a tiny scale on the CPU."""
import io
import json

import pytest

import bench_cpu
from bench import spans, tracing


class _Span:
    def __init__(self, name, t0, t1, track="engine"):
        self.name, self.t0, self.t1, self.track = name, t0, t1, track


def test_map_spans_takes_out_offset_and_rate_drift():
    # the profiler's clock: another origin, and 20 ppm fast
    prof = lambda t: 5e6 + (t - 100.0) * 1e9 * (1 + 2e-5)
    anchors = [(100.0, prof(100.0)), (110.0, prof(110.0))]
    recorded = [_Span("step", 103.0, 103.02),
                _Span("dispatch", 109.5, 109.9),
                _Span("queued", 104.0, 104.1, track="query"),
                _Span("step", 105.0, None)]
    mapped = spans.map_spans(recorded, anchors)
    assert [m[0] for m in mapped] == ["serve/step", "serve/dispatch"]
    for (name, s, d), sp in zip(mapped, recorded):
        assert s == pytest.approx(prof(sp.t0), abs=1e-3)
        assert s + d == pytest.approx(prof(sp.t1), abs=1e-3)
    # the first anchor's offset alone would put the dispatch 0.19 ms early
    assert mapped[1][1] - (5e6 + 9.5e9) == pytest.approx(1.9e5, abs=1)
    assert spans.map_spans(recorded, [anchors[0], anchors[0]]) == []


def test_clock_check_measures_the_step_outside_its_annotation():
    host = [("bench/step", 0, 1000), ("bench/step", 2000, 1000)]
    mapped = [("serve/step", 10, 980), ("serve/step", 2050, 1000),
              ("serve/dispatch", 0, 5000)]
    assert spans.clock_check(mapped, host) == (2, 50)
    assert spans.clock_check(mapped[:1], host) == (1, -10)


def test_breakdown_names_a_gap_by_the_innermost_program_span():
    ms = 1_000_000
    host = [("bench/step", 0, 10 * ms), ("bench/submit", 10 * ms, 6 * ms),
            ("serve/step", ms // 2, 9 * ms),
            ("serve/dispatch", 1 * ms, 7 * ms),
            ("serve/dispatch.launch", 1 * ms, 1 * ms),
            ("serve/dispatch.wait", 2 * ms, 4 * ms),
            ("serve/dispatch.fetch", 6 * ms, 3 * ms // 2),
            ("serve/deliver", 8 * ms, 1 * ms)]
    ops = [("s0_scan/fusion.2", 3 * ms // 2, ms // 2),
           ("s1_mapsin/while.1", 2 * ms, 4 * ms),
           ("s1_mapsin/fusion.3", 3 * ms, 1 * ms),     # inside the loop
           ("copy.4", 6 * ms, ms // 4),
           # a loop the compiler made, with no scope, around a scoped op
           ("while.9", 10 * ms, 4 * ms), ("s2_mapsin/fusion.5", 11 * ms, ms)]
    r = spans.breakdown(ops, host)
    gaps = dict(r["idle_by_span"])
    # idle 0-1.5, 6.25-10 and 14-16 ms, each part under its innermost span
    assert gaps["serve/dispatch.fetch"] == pytest.approx(0.00125)
    assert gaps["serve/dispatch.launch"] == pytest.approx(0.0005)
    assert gaps["serve/step"] == pytest.approx(0.001)
    assert gaps["serve/dispatch"] == pytest.approx(0.0005)
    assert gaps["serve/deliver"] == pytest.approx(0.001)
    assert gaps["bench/step"] == pytest.approx(0.001)
    assert gaps["bench/submit"] == pytest.approx(0.002)
    assert r["busy_s"] == pytest.approx(0.00875)
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["idle_unnamed_s"] == pytest.approx(0.003)
    scopes = dict(r["device_scopes"])
    # every busy instant counted once: a loop's body op in its loop's
    # scope, the unscoped loop's own time apart from its scoped op
    assert scopes == pytest.approx({"s1_mapsin": 0.004, "s0_scan": 0.0005,
                                    "s2_mapsin": 0.001,
                                    "unscoped": 0.00325})
    assert sum(scopes.values()) == pytest.approx(r["busy_s"])
    assert dict(r["device_ops"])["s1_mapsin/fusion.3"] == pytest.approx(
        0.001)


def _xspace_text() -> str:
    """A TPU-shaped XSpace: two programs whose op `%while.5 = ...` has
    the same text and runs in different cascade steps, an op with no
    framework name, and the host's annotations."""
    def meta(i, name, program, op=None):
        stats = f"stats {{ metadata_id: 1 uint64_value: {program} }}"
        if op:
            stats += f' stats {{ metadata_id: 2 str_value: "{op}" }}'
        return (f'event_metadata {{ key: {i} value {{ id: {i} '
                f'name: "{name}" {stats} }} }}')

    def ev(i, t_ns, d_ns):
        return (f"events {{ metadata_id: {i} offset_ps: {t_ns * 1000} "
                f"duration_ps: {d_ns * 1000} }}")

    w = "%while.5 = (s32[]) while(s32[] %t)"
    device = "\n".join([
        'planes { id: 1 name: "/device:TPU:0"',
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0',
        ev(10, 0, 100), ev(11, 200, 100), "}",
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0',
        ev(1, 10, 50), ev(2, 70, 20), ev(3, 210, 50), ev(4, 280, 10), "}",
        meta(10, "jit_batched(111)", 111), meta(11, "jit_batched(222)", 222),
        meta(1, w, 111, "jit(batched)/vmap(cascade/s1_mapsin)/while"),
        meta(2, "%copy.1 = s32[4] copy(s32[4] %p)", 111),
        meta(3, w, 222, "jit(batched)/vmap(cascade/s2_multiway)/while"),
        meta(4, "%fusion.7 = s32[4] fusion(s32[4] %p)", 222,
             "jit(batched)/mul"),
        'stat_metadata { key: 1 value { id: 1 name: "program_id" } }',
        'stat_metadata { key: 2 value { id: 2 name: "tf_op" } }', "}"])
    host = "\n".join([
        'planes { id: 2 name: "/host:CPU"',
        'lines { id: 1 name: "python3" timestamp_ns: 0',
        ev(1, 0, 1), ev(2, 5, 290), ev(3, 60, 100), ev(1, 299, 1), "}",
        'event_metadata { key: 1 value { id: 1 name: "trace_clock" } }',
        'event_metadata { key: 2 value { id: 2 name: "bench/step" } }',
        'event_metadata { key: 3 value { id: 3 name: "jit_one" } }', "}"])
    return device + "\n" + host


def test_load_xplane_reads_op_scopes_from_event_metadata(tmp_path):
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(_xspace_text())
    (tmp_path / "host.xplane.pb").write_bytes(data)
    ops, host, anchors, inventory = spans.load_xplane(str(tmp_path))
    assert [n for n, _, _ in ops] == [
        "s1_mapsin/while.5", "copy.1", "s2_multiway/while.5", "fusion.7"]
    assert [s for _, s, _ in ops] == [10, 70, 210, 280]
    assert host == [("bench/step", 5, 290)]
    assert anchors == [("trace_clock", 0, 1), ("trace_clock", 299, 1)]
    assert "'tf_op': 3 of 6 ops; 2 of 4 op events" in inventory
    r = spans.breakdown(ops, host)
    assert dict(r["device_scopes"]) == pytest.approx(
        {"s1_mapsin": 5e-8, "s2_multiway": 5e-8, "unscoped": 3e-8})


def test_mapped_step_spans_lie_inside_their_annotations(tmp_path):
    """On the CPU, under a real profiler session: the engine's step spans,
    mapped through the two clock anchors, fall inside the loop's
    ``bench/step`` annotations."""
    import jax
    import numpy as np

    from bench import loops
    from repro.core import Pattern, build_store
    from repro.obs import MetricsRegistry, Tracer
    from repro.serve import ServeEngine
    rng = np.random.RandomState(0)
    tr = np.stack([rng.randint(0, 40, 400), rng.randint(100, 103, 400),
                   rng.randint(0, 40, 400)], 1).astype(np.int32)
    tracer = Tracer()
    eng = ServeEngine(build_store(tr), tracer=tracer,
                      metrics=MetricsRegistry())
    chain = [Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z")]
    eng.execute([chain])                               # compile outside
    reads = []
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(spans.ANCHOR):
        reads.append(tracer.now())
    served = loops.closed_loop(eng, lambda k: chain, 1, 0.3,
                               loops.Hooks(annotate=True))
    with jax.profiler.TraceAnnotation(spans.ANCHOR):
        reads.append(tracer.now())
    jax.profiler.stop_trace()
    _, host, anchors, _ = spans.load_xplane(str(tmp_path))
    assert len(anchors) == 2
    steps = [sp for sp in tracer.spans
             if sp.name == "step" and sp.t0 >= served.t0]
    mapped = spans.map_spans(
        steps, [(t, s + d / 2) for t, (_, s, d) in zip(reads, anchors)])
    n, worst = spans.clock_check(mapped, host)
    assert n == len(steps) >= 10
    assert worst < 100_000                              # ns
    ann = [(s, s + d) for name, s, d in host if name == "bench/step"]
    for _, s, d in mapped:
        assert any(a0 <= s + d / 2 <= a1 for a0, a1 in ann)


def test_breakdown_agrees_with_the_benchmarks_reduction():
    """On a trace recorded on one TPU v5e chip: the same window and busy
    time as ``bench/tracing.reduce``, and every idle second named once."""
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "trace_v5e_sample.json")) as f:
        rec = json.load(f)
    r = tracing.reduce(rec["ops"], rec["host"])
    b = spans.breakdown(rec["ops"], rec["host"])
    assert b["window_s"] == pytest.approx(r["window_s"], rel=1e-12)
    assert b["busy_s"] == pytest.approx(r["busy_s"], rel=1e-12)
    assert b["idle_s"] == pytest.approx(b["window_s"] - b["busy_s"])
    assert sum(v for _, v in b["idle_by_span"]) == pytest.approx(
        b["idle_s"])
    assert dict(b["device_scopes"]) == pytest.approx(
        {spans.UNSCOPED: b["busy_s"]})


def test_one_traced_window_at_a_tiny_scale():
    out, err = io.StringIO(), io.StringIO()
    res = spans.run(bench_cpu.ROOT, bench_cpu.WORKLOAD, 2147483711, 1.0,
                    bench_cpu.cpu_device, overrides=bench_cpu.tiny_config(),
                    traffic_overrides=bench_cpu.TINY_TRAFFIC, out=out,
                    err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == res
    assert res["requests"] > 0
    means = res["spans"]
    n_disp = means["dispatch"][0]
    for name in ("dispatch.launch", "dispatch.wait", "dispatch.fetch",
                 "deliver"):
        assert means[name][0] == n_disp, name
    phases = sum(means[p][1] for p in ("dispatch.launch", "dispatch.wait",
                                       "dispatch.fetch"))
    assert phases == pytest.approx(means["dispatch"][1], rel=0.02)
    assert means["fetch_bytes"] > 0
    # the CPU has no device plane: the clock is still checked
    assert res["clock"]["steps_checked"] == means["step"][0]
    assert res["clock"]["largest_outside_us"] < 100
