"""The client loop that drives ``ServeEngine`` in the window.

It runs on one thread, as the engine does: ``submit`` parses and plans
on the caller's thread, ``step`` dispatches one batched cascade and
returns the answers it completed. Times are ``time.perf_counter``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

# how long past the window's close the loop waits for answers in flight
GRACE_S = 60.0


@dataclasses.dataclass
class Served:
    """What the window did to each request, by its index in the stream."""
    submitted: list             # submit start time
    delivered: list             # delivery time (None: never answered)
    results: dict               # index -> the QueryResult delivered
    refused: list               # indices the engine refused (EngineBusy)
    t0: float = 0.0             # window open
    t1: float = 0.0             # window close (t0 + seconds)
    t_end: float = 0.0          # last answer delivered


class Hooks:
    """Optional host annotations for the profiler (``bench/submit``,
    ``bench/step``)."""

    def __init__(self, annotate: bool = False):
        if annotate:
            import jax
            self.span = jax.profiler.TraceAnnotation
        else:
            self.span = lambda name: contextlib.nullcontext()


def closed_loop(eng, next_text, clients: int, seconds: float,
                hooks: Hooks, busy_exc=Exception) -> Served:
    """`clients` clients each send a request, wait for its answer and send
    the next, until the window closes; answers still in flight then are
    served to completion (and checked) but count for nothing."""
    s = Served([], [], {}, [])
    clock = time.perf_counter
    t0 = s.t0 = clock()
    s.t1 = t0 + seconds
    rid_of: dict[int, tuple[int, int]] = {}

    def send(client: int) -> None:
        k = len(s.submitted)
        now = clock()
        s.submitted.append(now)
        s.delivered.append(None)
        try:
            with hooks.span("bench/submit"):
                rid_of[eng.submit(next_text(k))] = (k, client)
        except busy_exc:
            s.refused.append(k)

    for c in range(clients):
        send(c)
    deadline = s.t1 + GRACE_S
    while rid_of:
        with hooks.span("bench/step"):
            out = eng.step()
        t = clock()
        for r in out:
            k, c = rid_of.pop(r.request_id)
            s.delivered[k] = t
            s.results[k] = r
            if t < s.t1:
                send(c)
        if t > deadline:
            break
    s.t_end = clock()
    return s
