"""Pallas searchsorted kernel (interpret) vs oracle — shape/dtype sweep."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the suite still runs
    from _hypothesis_stub import given, settings, st

from repro.core.rdf import pack3
from repro.kernels import ops
from repro.kernels.searchsorted import searchsorted3


@pytest.mark.parametrize("m,q", [(1, 1), (100, 7), (1000, 257), (5000, 333),
                                 (65536, 1024)])
def test_packed_sweep(m, q, rng):
    keys = np.sort(pack3(rng.randint(0, 2000, m), rng.randint(0, 50, m),
                         rng.randint(0, 2000, m)))
    qs = pack3(rng.randint(0, 2100, q), rng.randint(0, 55, q),
               rng.randint(0, 2100, q))
    import jax.numpy as jnp
    got = np.asarray(ops.searchsorted(jnp.asarray(keys), jnp.asarray(qs),
                                      interpret=True))
    want = np.searchsorted(keys, qs)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("block_k,block_q", [(64, 16), (256, 64), (2048, 256)])
def test_block_shapes(block_k, block_q, rng):
    m, q = 3000, 100
    k3 = np.sort(rng.randint(0, 500, (m, 3)).astype(np.int32).view(np.int32), axis=0)
    # build lexicographically sorted rows properly
    rows = rng.randint(0, 500, (m, 3)).astype(np.int32)
    order = np.lexsort((rows[:, 2], rows[:, 1], rows[:, 0]))
    rows = rows[order]
    qs = rng.randint(0, 550, (q, 3)).astype(np.int32)
    import jax.numpy as jnp
    got = np.asarray(searchsorted3(jnp.asarray(rows), jnp.asarray(qs),
                                   block_k=block_k, block_q=block_q,
                                   interpret=True))
    packed = (rows[:, 0].astype(np.int64) << 42) | \
             (rows[:, 1].astype(np.int64) << 21) | rows[:, 2]
    pq = (qs[:, 0].astype(np.int64) << 42) | \
         (qs[:, 1].astype(np.int64) << 21) | qs[:, 2]
    want = np.searchsorted(packed, pq)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 700),
       q=st.integers(1, 130))
def test_property_vs_oracle(seed, m, q):
    rng = np.random.RandomState(seed)
    keys = np.sort(pack3(rng.randint(0, 80, m), rng.randint(0, 8, m),
                         rng.randint(0, 80, m)))
    qs = pack3(rng.randint(0, 90, q), rng.randint(0, 9, q),
               rng.randint(0, 90, q))
    import jax.numpy as jnp
    got = np.asarray(ops.searchsorted(jnp.asarray(keys), jnp.asarray(qs),
                                      block_k=64, block_q=32,
                                      interpret=True))
    np.testing.assert_array_equal(got, np.searchsorted(keys, qs))


def test_boundary_duplicates():
    """Duplicate keys + probes hitting exact boundaries ('left' semantics)."""
    import jax.numpy as jnp
    keys = np.array([5, 5, 5, 7, 7, 9], np.int64)
    qs = np.array([4, 5, 6, 7, 8, 9, 10], np.int64)
    got = np.asarray(ops.searchsorted(jnp.asarray(keys), jnp.asarray(qs),
                                      block_k=64, block_q=32,
                                      interpret=True))
    np.testing.assert_array_equal(got, np.searchsorted(keys, qs))
