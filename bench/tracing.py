"""From a profiler trace to device busy time and an idle breakdown.

The reduction works on plain event lists, ``(name, start_ns, dur_ns)``,
so that it can be checked on a small recorded trace; ``load_xplane``
takes those lists out of the ``.xplane.pb`` that ``jax.profiler`` writes.
"""
from __future__ import annotations

import glob
import os

# host annotations the idle gaps are attributed to: the benchmark's own
# (``bench/...``) and the engine's dispatch brackets
HOST_PREFIXES = ("bench/", "serve_dispatch/")
# device lines that hold one event per executed operation, most precise
# first
OP_LINES = ("XLA Ops", "XLA Modules")


def op_name(hlo: str) -> str:
    """``%while.28 = (...) while(...)`` -> ``while.28``: the instruction's
    name without its shapes and operands."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load_xplane(trace_dir: str, device: str = "/device:TPU:0"):
    """(device_ops, host_spans, inventory) from the newest ``.xplane.pb``
    under `trace_dir`: the operations on `device`'s op line, the host
    annotations named by HOST_PREFIXES, and a short description of the
    planes and lines found (for the log)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return [], [], "no xplane.pb written"
    pd = ProfileData.from_file(paths[-1])
    ops: list[tuple[str, float, float]] = []
    host: list[tuple[str, float, float]] = []
    inventory = []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        inventory.append(f"{plane.name}: {sorted(lines)[:8]}")
        if plane.name == device:
            for name in OP_LINES:
                if name in lines:
                    ops = [(op_name(e.name), e.start_ns, e.duration_ns)
                           for e in lines[name].events]
                    break
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return ops, host, "; ".join(inventory)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(ops, host, top: int = 10) -> dict | None:
    """Busy and idle time of one device over the traced window.

    The window runs from the first to the last end of the benchmark's own
    host annotations (``bench/...``), which cover the whole loop. Busy is
    the union of the device's operation intervals inside it. Each idle
    gap is attributed to the innermost host annotation that covers its
    midpoint ("no host span" when none does). Returns None where the
    trace holds no window or no device operation."""
    bench = [(s, s + d) for n, s, d in host if n.startswith("bench/")]
    if not bench or not ops:
        return None
    w0 = min(s for s, _ in bench)
    w1 = max(e for _, e in bench)
    if w1 <= w0:
        return None
    clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in ops
               if s < w1 and s + d > w0]
    busy = _union(clipped)
    busy_ns = sum(e - s for s, e in busy)
    if busy_ns <= 0:
        return None
    by_op: dict[str, float] = {}
    for n, s, d in ops:
        if s < w1 and s + d > w0:
            by_op[n] = by_op.get(n, 0.0) + (min(s + d, w1) - max(s, w0))
    gaps = []
    cur = w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    spans = sorted(host, key=lambda h: h[2])        # shortest (innermost) first
    by_host: dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        name = next((n for n, hs, hd in spans if hs <= mid <= hs + hd),
                    "no host span")
        by_host[name] = by_host.get(name, 0.0) + (e - s)
    rank = lambda d: sorted(([k, v * 1e-9] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns * 1e-9, "window_s": (w1 - w0) * 1e-9,
            "device_ops": rank(by_op), "idle_gaps": rank(by_host)}
