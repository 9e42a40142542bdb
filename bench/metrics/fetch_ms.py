"""Device-to-host copy: mean of the engine's ``dispatch.fetch`` spans (the
copy of a finished dispatch's output arrays to the host)."""


def read(w):
    return w.span_mean_ms("dispatch.fetch")
