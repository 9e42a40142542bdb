"""Scheduler: share of the dispatch time whose answers were thrown away,
in percent. Each ``dispatch`` span's time is split evenly over its ``n``
requests; the part of the requests it re-enqueued at larger caps
(``escalated``) is wasted."""


def read(w):
    disp = [sp for sp in w.spans if sp.name == "dispatch"]
    if not any("escalated" in sp.attrs for sp in disp):
        return None
    total = sum(sp.t1 - sp.t0 for sp in disp)
    if total <= 0:
        return None
    wasted = sum((sp.t1 - sp.t0) * sp.attrs.get("escalated", 0)
                 / sp.attrs["n"] for sp in disp)
    return 100.0 * wasted / total
