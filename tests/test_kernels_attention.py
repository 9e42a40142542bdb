"""Pallas flash attention (interpret) + XLA blockwise impls vs reference."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import attention as attn


CASES = [
    # (b, sq, skv, h, g, e, causal)
    (2, 128, 128, 4, 4, 64, True),
    (1, 256, 256, 8, 2, 32, True),
    (2, 96, 160, 4, 1, 16, False),
    (1, 64, 64, 2, 2, 128, True),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_flash_vs_ref(case, dtype, rng):
    b, sq, skv, h, g, e, causal = case
    if causal and sq != skv:
        pytest.skip("kernel causal mask assumes aligned sq == skv")
    q = jnp.asarray(rng.randn(b, sq, h, e), dtype)
    k = jnp.asarray(rng.randn(b, skv, g, e), dtype)
    v = jnp.asarray(rng.randn(b, skv, g, e), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64,
                              interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                 want.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("impl", ["xla", "xla_tri"])
@pytest.mark.parametrize("case", CASES)
def test_xla_blockwise_vs_naive(impl, case, rng):
    b, sq, skv, h, g, e, causal = case
    q = jnp.asarray(rng.randn(b, sq, h, e), jnp.float32)
    k = jnp.asarray(rng.randn(b, skv, g, e), jnp.float32)
    v = jnp.asarray(rng.randn(b, skv, g, e), jnp.float32)
    got = attn.attention(q, k, v, impl=impl, causal=causal, block_q=32,
                         block_kv=32)
    want = attn.naive_attention(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_local_window_vs_naive(rng):
    b, s, h, g, e, w = 2, 128, 4, 1, 32, 48
    q = jnp.asarray(rng.randn(b, s, h, e), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, g, e), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, g, e), jnp.float32)
    got = attn.local_attention(q, k, v, window=w, block_q=32)
    want = attn.naive_attention(q, k, v, causal=True, window=w)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_decode_matches_prefill_row(rng):
    """decode_attention(q_t, cache) == last row of full causal attention."""
    b, s, h, g, e = 2, 33, 4, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, e), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, g, e), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, g, e), jnp.float32)
    full = attn.naive_attention(q, k, v, causal=True)
    one = attn.decode_attention(q[:, -1:], k, v, cur_len=s)
    assert float(jnp.max(jnp.abs(one[:, 0] - full[:, -1]))) < 2e-5
