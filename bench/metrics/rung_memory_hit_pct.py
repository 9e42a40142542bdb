"""Scheduler: share of the dispatched requests that entered at a rung the
engine remembered from an earlier overflow of the same query, in percent:
100 × Σ ``remembered`` / Σ ``n`` over the window's ``dispatch`` spans."""


def read(w):
    disp = [sp for sp in w.spans if sp.name == "dispatch"]
    if not any("remembered" in sp.attrs for sp in disp):
        return None
    n = sum(sp.attrs["n"] for sp in disp)
    if n <= 0:
        return None
    return 100.0 * sum(sp.attrs.get("remembered", 0) for sp in disp) / n
