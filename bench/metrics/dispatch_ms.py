"""Cascade: mean of the engine's ``dispatch`` spans (the batched cascade
on the device and the copy of its answers to the host)."""


def read(w):
    return w.span_mean_ms("dispatch")
