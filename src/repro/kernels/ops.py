"""jit'd public wrappers around the Pallas kernels.

The kernels compile natively for TPU by default (``interpret=False``).
Mosaic custom calls do not lower on the CPU backend, so a CPU caller must
ask for the interpreter explicitly (``interpret=True``, or
``ExecConfig(impl="pallas_interpret")``) — nothing here falls back to it on
its own. The jnp paths in models/ and core/ are numerically identical
(validated in tests/test_kernels_*.py and tests/test_probe_gather.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.rdf import BITS, MAX_ID
from repro.kernels import flash_attention as _fa
from repro.kernels import probe_gather as _pg
from repro.kernels import searchsorted as _ss


def unpack_to_cols(keys: jax.Array) -> jax.Array:
    """Packed int64 composite keys -> (N, 3) int32 lexicographic columns."""
    k = keys.astype(jnp.int64)
    mask = jnp.int64(MAX_ID)
    # INF_KEY padding maps to all-max columns (stays a +inf sentinel)
    c0 = jnp.minimum((k >> (2 * BITS)) & ((1 << 22) - 1), MAX_ID + 1)
    c1 = (k >> BITS) & mask
    c2 = k & mask
    return jnp.stack([c0, c1, c2], -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_k", "block_q"))
def searchsorted(keys: jax.Array, queries: jax.Array, *,
                 interpret: bool = False, block_k: int = 2048,
                 block_q: int = 256) -> jax.Array:
    """Drop-in for jnp.searchsorted(keys, queries) on packed int64 keys."""
    return _ss.searchsorted3(unpack_to_cols(keys), unpack_to_cols(queries),
                             block_k=block_k, block_q=block_q,
                             interpret=interpret).astype(jnp.int64)


@functools.partial(jax.jit,
                   static_argnames=("cap", "flt_mask", "eq_positions",
                                    "interpret", "block_k", "block_q"))
def probe_gather(keys: jax.Array, lo: jax.Array, hi: jax.Array,
                 flt: jax.Array, *, cap: int,
                 flt_mask: tuple = (False, False, False),
                 eq_positions: tuple = (), interpret: bool = False,
                 block_k: int = 2048, block_q: int = 256):
    """Fused MAPSIN probe on packed int64 keys — drop-in for the jnp
    gather_range + apply_residual pair in core/mapsin.py `probe`.

    Returns (k (B, cap) int64 packed match keys, 0 where invalid;
    valid (B, cap) bool; missed (B,) int32)."""
    match3, valid, missed = _pg.probe_gather3(
        unpack_to_cols(keys), unpack_to_cols(lo), unpack_to_cols(hi),
        flt.astype(jnp.int32), cap=cap, flt_mask=flt_mask,
        eq_positions=eq_positions, block_k=block_k, block_q=block_q,
        interpret=interpret)
    k = ((match3[..., 0].astype(jnp.int64) << (2 * BITS))
         | (match3[..., 1].astype(jnp.int64) << BITS)
         | match3[..., 2].astype(jnp.int64))
    return jnp.where(valid, k, 0), valid, missed


@functools.partial(jax.jit,
                   static_argnames=("causal", "interpret", "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False,
                    block_q: int = 512, block_kv: int = 512):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=interpret)
