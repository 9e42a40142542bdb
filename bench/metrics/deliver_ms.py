"""Scheduler: mean of the engine's ``deliver`` spans (the per-request loop
after a dispatch: answers cut from the tables, escalations re-enqueued,
exact fallbacks run)."""


def read(w):
    return w.span_mean_ms("deliver")
