"""chip_smoke.py on the CPU: its numpy reference against the oracle, its
refusal to run without a TPU, and its phases 2-5 at one university (the
Pallas kernels interpreted, since they only compile for a TPU)."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import Pattern, build_store, execute_oracle
from repro.core.planner import order_patterns
from repro.data import lubm_like

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lubm1():
    return lubm_like(1)


BGPS = {
    "chain": [Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z")],
    "star_const": [Pattern("?x", 101, "?y"), Pattern("?x", 102, "?z"),
                   Pattern("?x", 103, 5)],
    "repeat_var": [Pattern("?x", "?p", "?x")],
    "cartesian": [Pattern("?x", 101, 4), Pattern("?z", 102, "?w")],
    "const_subject": [Pattern(3, "?p", "?o")],
    "cycle": [Pattern("?x", 101, "?y"), Pattern("?y", "?p", "?x")],
    "no_match": [Pattern("?x", 101, "?y"), Pattern("?y", 104, 99)],
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", sorted(BGPS))
def test_reference_matches_oracle_random(cs, name, seed):
    rng = np.random.RandomState(seed)
    n = 400
    tr = np.stack([rng.randint(0, 30, n), rng.randint(100, 105, n),
                   rng.randint(0, 30, n)], 1).astype(np.int32)
    tr = np.concatenate([tr, tr[:20]])          # duplicates: set semantics
    want, ovars = execute_oracle(tr, BGPS[name])
    assert cs.reference_rows(tr, BGPS[name], ovars) == want


@pytest.mark.parametrize("q", ["Q1", "Q3", "Q4", "Q5", "Q7", "Q11", "Q13"])
def test_reference_matches_oracle_lubm(cs, lubm1, q):
    """The LUBM queries chip_smoke serves (Q8's nested-loop oracle takes
    minutes even at one university; its join shapes are covered by the
    random cases)."""
    triples, _, queries = lubm1
    pats = order_patterns(queries[q], store=build_store(triples))
    want, ovars = execute_oracle(triples, pats)
    assert want
    assert cs.reference_rows(triples, queries[q], ovars) == want


def test_refuses_to_run_without_a_tpu():
    """No accelerator: nonzero exit, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_phases_on_cpu(cs, capsys):
    """Phases 2-5 at one university: every served and kernel row set
    equals the reference (the phases raise otherwise)."""
    triples, d, store = cs.phase_load(1, seed=0)
    cs.phase_serve(store, d, triples, 1, burst=16, seed=0)
    cs.phase_kernels(store, d, triples, impl="pallas_interpret")
    cs.phase_durable(seed=0, n_universities=1, batch=1024)
    out = capsys.readouterr().out
    assert "triples=12937" in out
    assert out.count("complete=True") >= 2 * 8 + 16
    assert "complete=False" not in out
