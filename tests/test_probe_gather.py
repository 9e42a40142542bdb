"""Fused Pallas probe_gather (interpret) vs the jnp reference probe path.

The acceptance contract for kernels/probe_gather.py: identical match keys
(at valid slots), identical validity masks, identical per-probe missed
counts — on random stores and patterns, including empty ranges, residual
filters, intra-pattern variable repeats, fat rows, and overflow."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ExecConfig, Pattern, build_store, execute_local, \
    execute_oracle, rows_set
from repro.core.mapsin import apply_residual, gather_range, probe
from repro.core.plan import make_plan
from repro.core.rdf import pack3
from repro.kernels import ops


def _jnp_reference(keys, lo, hi, flt, msk, eq, cap):
    k, valid, missed = gather_range(keys, lo, hi, cap)
    valid = apply_residual(k, valid, flt, msk, eq)
    return np.where(np.asarray(valid), np.asarray(k), 0), \
        np.asarray(valid), np.asarray(missed)


def _fused(keys, lo, hi, flt, msk, eq, cap, block_k=256, block_q=32):
    k, valid, missed = ops.probe_gather(keys, lo, hi, flt, cap=cap,
                                        flt_mask=msk, eq_positions=eq,
                                        interpret=True, block_k=block_k,
                                        block_q=block_q)
    return np.asarray(k), np.asarray(valid), np.asarray(missed)


def _check(keys, lo, hi, flt, msk, eq, cap, **kw):
    kr, vr, mr = _jnp_reference(keys, lo, hi, flt, msk, eq, cap)
    kg, vg, mg = _fused(keys, lo, hi, flt, msk, eq, cap, **kw)
    np.testing.assert_array_equal(vr, vg, err_msg="validity mask")
    np.testing.assert_array_equal(kr, kg, err_msg="match keys")
    np.testing.assert_array_equal(mr, mg, err_msg="missed counts")


@pytest.mark.parametrize("seed", range(5))
def test_random_equivalence(seed):
    """Random sorted stores x random probe ranges x random residuals."""
    rng = np.random.RandomState(seed)
    m = rng.randint(50, 4000)
    b = rng.randint(1, 200)
    cap = int(rng.choice([1, 2, 8, 16]))
    keys = jnp.asarray(np.sort(pack3(rng.randint(0, 40, m),
                                     rng.randint(0, 6, m),
                                     rng.randint(0, 40, m))))
    v = rng.randint(0, 45, b).astype(np.int64)       # some miss entirely
    z = np.zeros(b, np.int64)
    lo = pack3(v, z, z)
    hi = pack3(v + 1, z, z)
    # a slice of probes with a (v, p) two-component prefix
    p2 = rng.randint(0, 6, b).astype(np.int64)
    two = rng.rand(b) < 0.3
    lo = np.where(two, pack3(v, p2, z), lo)
    hi = np.where(two, pack3(v, p2 + 1, z), hi)
    # some invalid/empty probes, as the executor emits for invalid rows
    empty = rng.rand(b) < 0.2
    lo, hi = np.where(empty, 0, lo), np.where(empty, 0, hi)
    flt = np.zeros((b, 3), np.int64)
    flt[:, 2] = rng.randint(0, 40, b)
    msk = (False, False, bool(seed % 2))             # residual on/off
    _check(keys, jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(flt), msk,
           (), cap)


def test_fat_row_overflow():
    """One fat subject owning >> cap triples: missed must count the spill."""
    n = 500
    s = np.zeros(n, np.int64)                        # all triples on subject 0
    p = np.arange(n, dtype=np.int64) % 3
    o = np.arange(n, dtype=np.int64) % 170
    keys = jnp.asarray(np.sort(pack3(s, p, o)))
    z = np.zeros(4, np.int64)
    lo = jnp.asarray(pack3(np.zeros(4, np.int64), z, z))
    hi = jnp.asarray(pack3(np.ones(4, np.int64), z, z))
    flt = jnp.asarray(np.zeros((4, 3), np.int64))
    cap = 8
    kr, vr, mr = _jnp_reference(keys, lo, hi, flt, (False,) * 3, (), cap)
    kg, vg, mg = _fused(keys, lo, hi, flt, (False,) * 3, (), cap)
    np.testing.assert_array_equal(vr, vg)
    np.testing.assert_array_equal(kr, kg)
    np.testing.assert_array_equal(mr, mg)
    assert mg.min() > 0                              # the spill IS surfaced


def test_empty_and_degenerate_ranges():
    keys = jnp.asarray(np.sort(pack3(
        np.array([1, 1, 2, 5], np.int64), np.array([0, 1, 0, 2], np.int64),
        np.array([3, 4, 5, 6], np.int64))))
    z = np.zeros(3, np.int64)
    lo = jnp.asarray(np.array([0, pack3(np.int64(3), 0, 0),
                               pack3(np.int64(9), 0, 0)], np.int64))
    hi = jnp.asarray(np.array([0, pack3(np.int64(4), 0, 0),
                               pack3(np.int64(10), 0, 0)], np.int64))
    flt = jnp.asarray(np.zeros((3, 3), np.int64))
    _check(keys, lo, hi, flt, (False,) * 3, (), 4)


def test_eq_positions_self_join():
    """Intra-pattern repeated variable (?x p ?x) as an eq-position filter."""
    rng = np.random.RandomState(7)
    m = 600
    keys = jnp.asarray(np.sort(pack3(rng.randint(0, 12, m),
                                     rng.randint(0, 4, m),
                                     rng.randint(0, 12, m))))
    b = 30
    v = rng.randint(0, 12, b).astype(np.int64)
    z = np.zeros(b, np.int64)
    lo = jnp.asarray(pack3(v, z, z))
    hi = jnp.asarray(pack3(v + 1, z, z))
    flt = jnp.asarray(np.zeros((b, 3), np.int64))
    _check(keys, lo, hi, flt, (False,) * 3, ((0, 2),), 8)


@pytest.mark.parametrize("block_k,block_q", [(64, 16), (512, 128)])
def test_block_shape_sweep(block_k, block_q):
    rng = np.random.RandomState(3)
    m, b, cap = 1500, 70, 4
    keys = jnp.asarray(np.sort(pack3(rng.randint(0, 30, m),
                                     rng.randint(0, 5, m),
                                     rng.randint(0, 30, m))))
    v = rng.randint(0, 30, b).astype(np.int64)
    z = np.zeros(b, np.int64)
    flt = np.zeros((b, 3), np.int64)
    flt[:, 1] = rng.randint(0, 5, b)
    _check(keys, jnp.asarray(pack3(v, z, z)), jnp.asarray(pack3(v + 1, z, z)),
           jnp.asarray(flt), (False, True, False), (), cap,
           block_k=block_k, block_q=block_q)


def test_probe_dispatch_matches_jnp():
    """core/mapsin.probe(impl='pallas_interpret') == probe(impl='jnp') on a
    real plan (prefix + residual filter from a cascading pattern)."""
    rng = np.random.RandomState(11)
    tr = np.stack([rng.randint(0, 25, 400), rng.randint(100, 104, 400),
                   rng.randint(0, 25, 400)], 1).astype(np.int32)
    store = build_store(tr, 1)
    keys = store.flat_keys(0)
    plan = make_plan(Pattern("?x", 101, "?y"), ("?x",))
    table = jnp.asarray(rng.randint(0, 25, (40, 1)), jnp.int32)
    valid = jnp.asarray(rng.rand(40) < 0.8)
    k_ref, v_ref, m_ref = probe(plan, keys, table, valid, 8, impl="jnp")
    k_got, v_got, m_got = probe(plan, keys, table, valid, 8,
                                impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_got))
    np.testing.assert_array_equal(
        np.where(np.asarray(v_ref), np.asarray(k_ref), 0), np.asarray(k_got))
    np.testing.assert_array_equal(np.asarray(m_ref), np.asarray(m_got))


def test_full_engine_pallas_interpret_vs_oracle():
    """End-to-end: the jitted cascade with the fused kernel == oracle."""
    rng = np.random.RandomState(5)
    tr = np.stack([rng.randint(0, 20, 250), rng.randint(100, 103, 250),
                   rng.randint(0, 20, 250)], 1).astype(np.int32)
    store = build_store(tr, 1)
    pats = [Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z")]
    from repro.core import Caps, compile_plan
    caps = Caps(scan_cap=2048, out_cap=4096, probe_cap=32)
    cfg = ExecConfig(impl="pallas_interpret")
    want, ovars = execute_oracle(tr, pats)
    plan = compile_plan(store, pats, caps, multiway=False)
    bnd = execute_local(store, plan, cfg=cfg)
    got = rows_set(bnd.table, bnd.valid, len(bnd.vars))
    if tuple(bnd.vars) != ovars:
        perm = [bnd.vars.index(v) for v in ovars]
        got = set(tuple(r[i] for i in perm) for r in got)
    assert int(bnd.overflow) == 0
    assert got == want


def test_pallas_impl_never_falls_back():
    """impl="pallas" means the compiled kernel: where Mosaic cannot lower
    (this CPU backend) it raises rather than quietly running jnp or the
    interpreter; an unknown impl is refused outright."""
    import jax
    from repro.core.mapsin import searchsorted
    keys = jnp.asarray(np.arange(0, 100, 3, dtype=np.int64))
    qs = jnp.asarray(np.array([4, 50], np.int64))
    np.testing.assert_array_equal(
        np.asarray(searchsorted(keys, qs, impl="pallas_interpret")),
        np.searchsorted(np.asarray(keys), np.asarray(qs)))
    if jax.default_backend() == "cpu":
        with pytest.raises(ValueError, match="interpret"):
            jax.block_until_ready(searchsorted(keys, qs, impl="pallas"))
    with pytest.raises(ValueError, match="unknown impl"):
        ExecConfig(impl="pallas_tpu")
