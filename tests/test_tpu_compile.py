"""Compile guards for the TPU main path: the Pallas kernels and a whole
LUBM cascade, compiled for a described (not attached) v5e chip at the
size ``chip_smoke.py`` serves — a LUBM-400 index. Nothing runs; the TPU
compiler refuses here what it would refuse on the chip (unaligned slices,
64-bit vectors in a kernel, too much VMEM, too much HBM).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, so the worker
that runs this file loads it and no other does."""
import importlib.util
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.common import compile_cache_off
from repro.core import Caps, ExecConfig, build_store, compile_plan
from repro.core.bgp import _cascade_body
from repro.core.mapsin import Bindings
from repro.data import lubm_like
from repro.kernels.probe_gather import probe_gather3
from repro.kernels.searchsorted import searchsorted3

N_KEYS = 12937 * 400             # lubm_like triples per university x 400
PROBE_BATCH = Caps().out_cap     # rows a cascade step probes with
HBM_BYTES = 16 * 10**9           # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """A v5e device described, not attached. Skips only where the TPU
    compiler library is not installed at all; any other failure to
    describe the chip fails the tests."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the libtpu package (TPU compiler) is not installed")
    from jax.experimental import topologies
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    try:
        with compile_cache_off():
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check_fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def test_searchsorted3_compiles(one_chip):
    c = jax.jit(lambda k, q: searchsorted3(k, q)).lower(
        _spec((N_KEYS, 3), jnp.int32, one_chip),
        _spec((PROBE_BATCH, 3), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()
    _check_fits(c)


@pytest.mark.parametrize("cap", [Caps().probe_cap, 128])
def test_probe_gather3_compiles(one_chip, cap):
    fn = lambda k, lo, hi, f: probe_gather3(
        k, lo, hi, f, cap=cap, flt_mask=(False, True, False),
        eq_positions=((0, 2),))
    probes = _spec((PROBE_BATCH, 3), jnp.int32, one_chip)
    c = jax.jit(fn).lower(_spec((N_KEYS, 3), jnp.int32, one_chip),
                          probes, probes, probes).compile()
    assert "tpu_custom_call" in c.as_text()
    _check_fits(c)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_lubm_q4_cascade_compiles(one_chip, impl):
    """LUBM Q4 (scan + multiway star) as execute_local compiles it, with
    the scratch Bindings donated as on a TPU, over index-sized keys."""
    triples, d, queries = lubm_like(1)
    plan = compile_plan(build_store(triples), queries["Q4"], Caps())
    assert [st.kind for st in plan.steps] == ["scan", "multiway"]
    fn, first_vars = _cascade_body(plan, ExecConfig(impl=impl))
    cap = plan.steps[0].caps.out_cap
    scratch = jax.eval_shape(lambda: Bindings.empty(first_vars, cap))
    scratch = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                           scratch)
    keys = _spec((N_KEYS,), jnp.int64, one_chip)
    c = jax.jit(fn, donate_argnums=(2,)).lower(keys, keys,
                                               scratch).compile()
    if impl == "pallas":
        assert "tpu_custom_call" in c.as_text()
    _check_fits(c)
