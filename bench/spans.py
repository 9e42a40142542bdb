"""The engine's spans on the device clock: one traced window of a cell,
with each idle stretch of the device named by the program span that held
the host, and the device time of each cascade step.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

It sets the cell up as ``bench/run.py --trace 1`` does (a traced engine,
warmed up), profiles the window, and prints one JSON object as the last
line of standard output. It checks no answers and reports no benchmark
metric.

The engine's ``Tracer`` stamps its spans with ``time.perf_counter``; the
profiler uses a clock of its own. The window is bracketed by two
zero-length ``trace_clock`` annotations, each holding a read of the
tracer's clock, and ``map_spans`` maps the spans linearly between the two
onto the profiler's clock, where they join the host annotations named
``serve/<span>``. ``breakdown`` splits each idle stretch of the device over
the spans covering it, each part to the innermost one; ops are keyed by
the cascade step scope (``cascade/s<i>_<kind>``) their framework op name
carries, which the profile keeps in the metadata of each op event
(``op_scopes``).
"""
from __future__ import annotations

import argparse
import bisect
import glob
import heapq
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# host annotations kept from the trace: the benchmark's loop and the
# engine's dispatch brackets
HOST_PREFIXES = ("bench/", "serve_dispatch/")
# the annotation the tracer's clock is read in
ANCHOR = "trace_clock"
# the prefix of a mapped engine span
SERVE = "serve/"
# device lines that hold one event per executed operation, most precise
# first
OP_LINES = ("XLA Ops", "XLA Modules")
# the engine's name scope of cascade step i in an op's framework name
SCOPE = re.compile(r"cascade/(s\d+_[a-z_]+)")
# the stats of an op's event metadata that hold its framework op name (the
# HLO ``op_name`` metadata, name scopes included) and its program
OP_NAME_STAT = "tf_op"
PROGRAM_STAT = "program_id"
# an op's key outside any cascade scope, in the scope breakdown
UNSCOPED = "unscoped"
# the engine's spans whose mean the result line gives
ENGINE_SPANS = ("submit", "step", "dispatch", "dispatch.launch",
                "dispatch.wait", "dispatch.fetch", "deliver")


# --------------------------------------------------------------------------
# reading the profile
# --------------------------------------------------------------------------


def _varint(buf, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized protobuf
    message: an int for a varint, a memoryview for a length-delimited
    field; fixed-width fields are skipped."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            v, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            v, pos = buf[pos:pos + n], pos + n
        elif kind in (1, 5):
            pos += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at {pos}")
        yield key >> 3, v


def _entry_value(entry):
    """The value (field 2) of a serialized protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_scopes(data: bytes, device: str) -> tuple[dict, int, int]:
    """({(program id, op event name): cascade step scope}, ops with an
    OP_NAME_STAT, ops) for `device`'s plane of a serialized XSpace.

    ``jax.profiler.ProfileData`` shows an event's own stats only, and the
    framework op name sits on the event's metadata, so the metadata is
    read from the protobuf itself, by field number (``xplane.proto``):
    XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5;
    map entries key 1, value 2; XEventMetadata.name 2, .stats 5;
    XStatMetadata.id 1, .name 2; XStat.metadata_id 1, uint64 3, int64 4,
    str 5, ref 7 (a stat metadata id whose name is the value)."""
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, metas, stat_meta = None, [], []
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                metas.append(v)
            elif g == 5:
                stat_meta.append(v)
        if name != device:
            continue
        stat_names = {}
        for entry in stat_meta:
            m = dict(_fields(_entry_value(entry)))
            stat_names[m.get(1)] = bytes(m.get(2, b"")).decode()
        scopes: dict[tuple, str] = {}
        named = 0
        for entry in metas:
            ev_name = program = op = None
            for g, v in _fields(_entry_value(entry)):
                if g == 2:
                    ev_name = bytes(v).decode()
                if g != 5:
                    continue
                stat_id = value = None
                for h, x in _fields(v):
                    if h == 1:
                        stat_id = x
                    elif h in (3, 4):
                        value = str(x)
                    elif h == 5:
                        value = bytes(x).decode()
                    elif h == 7:
                        value = stat_names.get(x)
                kind = stat_names.get(stat_id)
                if kind == PROGRAM_STAT:
                    program = value
                elif kind == OP_NAME_STAT:
                    op = value
            if op is None:
                continue
            named += 1
            m = SCOPE.search(op)
            if m:
                scopes[(program, ev_name)] = m.group(1)
        return scopes, named, len(metas)
    return {}, 0, 0


def _program(module: str) -> str | None:
    """``jit_one(1475...)`` -> ``1475...``: the program id an
    ``XLA Modules`` event names."""
    m = re.search(r"\((\d+)\)$", module)
    return m.group(1) if m else None


def load_xplane(trace_dir: str, device: str = "/device:TPU:0"):
    """(device_ops, host_spans, anchors, inventory) from the newest
    ``.xplane.pb`` under `trace_dir`: the operations on `device`'s op
    line, named ``<scope>/<hlo name>`` where the op ran in a cascade
    step's name scope; the host annotations named by HOST_PREFIXES; the
    ``trace_clock`` annotations in time order; and a short description of
    the ops' framework names (for the log)."""
    from jax.profiler import ProfileData

    from bench.tracing import op_name
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return [], [], [], "no xplane.pb written"
    with open(paths[-1], "rb") as f:
        data = f.read()
    scopes, named, n_meta = op_scopes(data, device)
    ops: list[tuple[str, float, float]] = []
    host: list[tuple[str, float, float]] = []
    anchors: list[tuple[str, float, float]] = []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name == device:
            mods = sorted((e.start_ns, _program(e.name))
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
            starts = [s for s, _ in mods]
            for name in OP_LINES:
                if name in lines:
                    for e in lines[name].events:
                        i = bisect.bisect_right(starts, e.start_ns) - 1
                        scope = scopes.get(
                            (mods[i][1] if i >= 0 else None, e.name))
                        hlo = op_name(e.name)
                        ops.append((f"{scope}/{hlo}" if scope else hlo,
                                    e.start_ns, e.duration_ns))
                    break
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name == ANCHOR:
                        anchors.append((e.name, e.start_ns, e.duration_ns))
    anchors.sort(key=lambda a: a[1])
    scoped = sum(1 for n, _, _ in ops if "/" in n)
    inventory = (f"framework op names from the metadata stat "
                 f"{OP_NAME_STAT!r}: {named} of {n_meta} ops; {scoped} of "
                 f"{len(ops)} op events in a cascade step scope")
    return ops, host, anchors, inventory


# --------------------------------------------------------------------------
# one clock
# --------------------------------------------------------------------------


def map_spans(spans, anchors) -> list[tuple[str, float, float]]:
    """The Tracer's engine-track spans on the profiler's clock, as host
    spans named ``serve/<span>``. `anchors` holds two ``(tracer_s,
    profiler_ns)`` pairs, the same instant read on both clocks; the map
    is the line through them, which takes out both the clocks' offset and
    any difference in their rates."""
    (ta, pa), (tb, pb) = anchors
    if tb <= ta:
        return []
    rate = (pb - pa) / (tb - ta)
    return [(SERVE + sp.name, pa + (sp.t0 - ta) * rate,
             (sp.t1 - sp.t0) * rate)
            for sp in spans if sp.track == "engine" and sp.t1 is not None]


def clock_check(mapped, host, span: str = "step",
                annotation: str = "bench/step") -> tuple[int, float]:
    """(spans checked, largest ns by which a mapped `span` falls outside
    the `annotation` that holds it; negative where every span lies
    inside, by at least that much): the annotation wraps the engine call
    that opens and closes the span, so on one clock the span lies inside
    it."""
    ann = sorted((s, s + d) for n, s, d in host if n == annotation)
    starts = [s for s, _ in ann]
    worst, n = float("-inf"), 0
    for name, s, d in mapped:
        if name != SERVE + span or not ann:
            continue
        i = max(bisect.bisect_right(starts, s + d / 2) - 1, 0)
        a0, a1 = ann[i]
        worst = max(worst, a0 - s, s + d - a1)
        n += 1
    return n, (worst if n else 0.0)


# --------------------------------------------------------------------------
# the breakdown
# --------------------------------------------------------------------------


def _innermost(host):
    """The timeline cut at both ends of every host span, each piece that
    some span covers named by the shortest span covering it:
    ``[(start, end, name)]`` in time order."""
    bounds = sorted({t for _, s, d in host if d > 0 for t in (s, s + d)})
    by_start = sorted((s, d, n) for n, s, d in host if d > 0)
    active: list[tuple[float, float, str]] = []     # (dur, end, name)
    out = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][0] <= a:
            s, d, n = by_start[i]
            heapq.heappush(active, (d, s + d, n))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            out.append((a, b, active[0][2]))
    return out


def breakdown(ops, host, top: int = 12) -> dict | None:
    """Busy and idle time of one device over the window, as
    ``bench/tracing.reduce`` defines them (the window spans the
    ``bench/`` annotations; busy is the union of the op intervals in it),
    with the idle split over the host spans: each part of an idle stretch
    goes to the innermost (shortest) span covering it, "no host span"
    where none does. ``idle_unnamed_s`` is the idle under a ``bench/``
    annotation or no span. Device time by op sums its events (a loop
    counts its body's ops too); by cascade step scope, each busy instant
    counts once, for the innermost scoped op covering it. None where the
    trace holds no window or no device operation."""
    from bench.tracing import _union
    bench = [(s, s + d) for n, s, d in host if n.startswith("bench/")]
    if not bench or not ops:
        return None
    w0 = min(s for s, _ in bench)
    w1 = max(e for _, e in bench)
    by_op: dict[str, float] = {}
    clipped, scoped = [], []
    for n, s, d in ops:
        if s < w1 and s + d > w0:
            a, b = max(s, w0), min(s + d, w1)
            by_op[n] = by_op.get(n, 0.0) + (b - a)
            clipped.append((a, b))
            if "/" in n:
                scoped.append((n.split("/", 1)[0], a, b - a))
    busy = _union(clipped)
    if w1 <= w0 or not busy:
        return None
    busy_ns = sum(e - s for s, e in busy)
    gaps = []
    cur = w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    pieces = _innermost(host)
    by_span: dict[str, float] = {}
    j = 0
    for s, e in gaps:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        covered = 0.0
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            t = min(b, e) - max(a, s)
            if t > 0:
                by_span[name] = by_span.get(name, 0.0) + t
                covered += t
            k += 1
        if e - s > covered:
            by_span["no host span"] = (by_span.get("no host span", 0.0)
                                       + (e - s) - covered)
    # ops nest (a loop's body ops lie inside the loop's event, and a loop
    # the compiler made carries no scope): each busy instant goes to the
    # innermost scoped op covering it, the rest to UNSCOPED
    by_scope: dict[str, float] = {}
    for a, b, scope in _innermost(scoped):
        by_scope[scope] = by_scope.get(scope, 0.0) + (b - a)
    by_scope[UNSCOPED] = busy_ns - sum(by_scope.values())
    rank = lambda d, n: sorted(([k, v * 1e-9] for k, v in d.items()),
                               key=lambda kv: -kv[1])[:n]
    return {"busy_s": busy_ns * 1e-9,
            "window_s": (w1 - w0) * 1e-9,
            "idle_s": sum(by_span.values()) * 1e-9,
            "idle_unnamed_s": sum(v for k, v in by_span.items()
                                  if k.startswith("bench/")
                                  or k == "no host span") * 1e-9,
            "idle_by_span": rank(by_span, top),
            "device_scopes": rank(by_scope, len(by_scope)),
            "device_ops": rank(by_op, top)}


def span_means(spans) -> dict:
    """{span name: [count, mean ms]} of ENGINE_SPANS, and the mean
    ``bytes`` of the ``dispatch.fetch`` spans."""
    out: dict = {}
    for name in ENGINE_SPANS:
        d = [sp.t1 - sp.t0 for sp in spans if sp.name == name]
        if d:
            out[name] = [len(d), 1e3 * sum(d) / len(d)]
    b = [sp.attrs["bytes"] for sp in spans
         if sp.name == "dispatch.fetch" and "bytes" in sp.attrs]
    if b:
        out["fetch_bytes"] = sum(b) / len(b)
    return out


# --------------------------------------------------------------------------
# one traced window
# --------------------------------------------------------------------------


def run(root: str, workload: str, seed: int, seconds: float,
        device_check, *, device: str = "/device:TPU:0",
        overrides: dict | None = None,
        traffic_overrides: dict | None = None,
        cache_dir: str | None = None, out=None, err=None) -> dict:
    """Set the cell up traced, profile one window between two clock
    anchors, and print the result line; returns it."""
    import jax

    from bench import harness, loops
    out = out or sys.stdout
    cell = harness.prepare(root, workload, seed, device_check,
                           overrides=overrides,
                           traffic_overrides=traffic_overrides, trace=True,
                           cache_dir=cache_dir, err=err)
    tracer = cell.eng.tracer
    trace_dir = tempfile.mkdtemp(prefix="bench_spans_")
    reads = []
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(ANCHOR):
        reads.append(tracer.now())
    served = harness.drive(cell, seconds, loops.Hooks(annotate=True))
    with jax.profiler.TraceAnnotation(ANCHOR):
        reads.append(tracer.now())
    jax.profiler.stop_trace()
    spans = [sp for sp in tracer.spans if sp.t0 >= served.t0]
    ops, host, anchors, inventory = load_xplane(trace_dir, device)
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.log(f"[trace] {len(ops)} device ops, {len(host)} host "
                f"annotations, {len(anchors)} clock anchors; {inventory}",
                err)
    result = {"requests": len(served.submitted),
              "delivered_in_window": sum(
                  1 for d in served.delivered
                  if d is not None and served.t0 <= d <= served.t1),
              "spans": span_means(spans)}
    mapped = []
    if len(anchors) == 2:
        mapped = map_spans(spans, [(t, s + d / 2) for t, (_, s, d)
                                   in zip(reads, anchors)])
        n, worst = clock_check(mapped, host)
        result["clock"] = {"anchor_us": [a[2] / 1e3 for a in anchors],
                           "steps_checked": n,
                           "largest_outside_us": worst / 1e3}
        harness.log(f"[trace] clock: anchors {anchors[0][2] / 1e3:.1f} and "
                    f"{anchors[1][2] / 1e3:.1f} us wide; {len(mapped)} "
                    f"engine spans mapped; of {n} step spans the largest "
                    f"outside its bench/step is {worst / 1e3:.3f} us "
                    "(negative: all inside)", err)
    b = breakdown(ops, host + mapped)
    if b is not None:
        result.update(b)
        share = 100 * b["idle_unnamed_s"] / b["idle_s"] if b["idle_s"] else 0
        result["idle_unnamed_pct"] = share
        harness.log(f"[trace] idle {b['idle_s']:.6f} s of "
                    f"{b['window_s']:.6f} s; under a bench/ annotation or "
                    f"no span {share:.2f}%; by span {b['idle_by_span']}; "
                    f"device s by step {b['device_scopes']}", err)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench.device import require_tpu
    run(ROOT, args.workload, args.seed, args.seconds, require_tpu,
        cache_dir=os.path.join(ROOT, ".bench_cache", "jax"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
