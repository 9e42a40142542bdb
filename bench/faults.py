"""Faults planted in the program's timed path, for the checks that the
comparison catches them (``bench/tests``, ``bench/control.py``). Each
``plant_*`` patches ``ServeEngine`` and returns a function that undoes
the patch."""
from __future__ import annotations

import numpy as np


def _patch(name: str, make):
    from repro.serve.engine import ServeEngine
    orig = getattr(ServeEngine, name)
    setattr(ServeEngine, name, make(orig))
    return lambda: setattr(ServeEngine, name, orig)


def plant_altered_answer():
    """An answer altered where it is produced: in every dispatch, each
    request's first solution gets its last column changed."""
    def make(orig):
        def broken(self, *a, **kw):
            tables, valids, ovf, step_ovf, bad = orig(self, *a, **kw)
            tables = np.array(tables)
            for i in range(tables.shape[1]):
                rows = np.nonzero(valids[0, i])[0]
                if len(rows):
                    tables[0, i, rows[0], -1] += 1
            return tables, valids, ovf, step_ovf, bad
        return broken
    return _patch("_dispatch", make)


def plant_half_batch_dropped():
    """Half of each dispatched batch left out, rounded up (one request of a
    batch of one): those answers never come."""
    def make(orig):
        def broken(self, reqs, now=None):
            out = orig(self, reqs, now)
            return out[:len(out) // 2]
        return broken
    return _patch("_run_bucket", make)


FAULTS = {"altered_answer": plant_altered_answer,
          "half_batch_dropped": plant_half_batch_dropped}
