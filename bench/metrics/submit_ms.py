"""Front end: mean of the engine's ``submit`` spans (SPARQL parse, plan
lookup or planning, admission)."""


def read(w):
    return w.span_mean_ms("submit")
