"""Cascade, host half: mean of the engine's ``dispatch.launch`` spans
(the compiled-cascade lookup, scratch, constants upload and enqueue, up to
the return of the jitted call)."""


def read(w):
    return w.span_mean_ms("dispatch.launch")
