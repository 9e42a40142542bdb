"""The device under test: the chip check, compile accounting, peaks."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def require_tpu(chips: int) -> dict:
    """A TPU with at least `chips` devices, or exit nonzero with no
    result line: the benchmark never falls back to the CPU."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"bench: needs a TPU, found {d0.platform!r} "
                 f"({len(devs)} device(s)); not running on the CPU")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU devices, found "
                 f"{len(devs)}")
    peaks(d0.device_kind)              # an unknown chip is an error
    return describe(devs[:chips])


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(kind: str) -> dict:
    """Published peaks of one chip of `kind` (``bench/peaks.json``); an
    unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"bench/peaks.json has no entry for device kind "
                       f"{kind!r}")
    return table[kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no statistics)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """XLA backend compiles (count and seconds) and persistent-cache hits
    and misses, from JAX's monitoring events (process-wide listeners).
    A copy of ``chip_smoke.CompileWatch`` that also counts compiles."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return (self.compiles, self.compile_s, self.cache_hits,
                self.cache_misses)

    def line(self, since: tuple = (0, 0.0, 0, 0)) -> str:
        c, s, h, m = (a - b for a, b in zip(self.snapshot(), since))
        return (f"compiles={c} compile_s={s:.2f} cache_hits={h} "
                f"cache_misses={m}")
