"""Device: share of the profiled part of the window in which no operation
ran on the chip, in percent (``bench/tracing.reduce``)."""


def read(w):
    t = w.device_trace
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
