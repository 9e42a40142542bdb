"""Scheduler: overflow escalations (re-dispatches at larger caps) per
request sent in the window (the engine's ``escalations`` counter)."""


def read(w):
    n = len(w.served.submitted)
    if n <= 0:
        return None
    return w.counters["escalations"] / n
