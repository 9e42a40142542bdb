"""Device-to-host copy: mean ``bytes`` of the engine's ``dispatch.fetch``
spans (the output arrays of one dispatch copied to the host)."""

import numpy as np


def read(w):
    b = [sp.attrs["bytes"] for sp in w.spans
         if sp.name == "dispatch.fetch" and "bytes" in sp.attrs]
    return float(np.mean(b)) if b else None
