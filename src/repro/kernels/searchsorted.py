"""Pallas TPU kernel: blocked lexicographic searchsorted — the index GET.

The MAPSIN hot-spot is rank-finding probes against the sorted composite-key
index (HBase GET -> binary search). A GPU port would do per-thread binary
search (divergent, gather-heavy); the TPU-native rethink (DESIGN.md §2):

  * keys live as THREE int32 columns (s, p, o in index order) — TPU has no
    native int64 vectors, and lexicographic compare on 3 x int32 is pure VPU.
    Inside the kernel the key block is lane-major ``(3, Bk)`` (one row per
    component) and the query block sublane-major ``(Bq, 3)``, so a
    ``(Bq, Bk)`` compare tile is a plain broadcast of a row against a
    column, and the per-query count is a lane reduction into ``(Bq, 1)``.
  * rank(q) = #{keys < q}, accumulated key-block by key-block over the grid.
  * sortedness is exploited with block bounds + `pl.when`: a query above
    the whole key block gains the block's size without elementwise work, a
    query below it gains zero, and only a block that some query of the tile
    falls inside pays the compare tile — the grid walks the index like a
    B-tree. The bounds are a vector max/min of the block's leading column.
  * every value in the kernel is int32, whatever ``jax_enable_x64`` says:
    Mosaic has no 64-bit vector types, so sums pin ``dtype=jnp.int32``,
    constants are numpy int32 (a Python int would be a weak int64) and the
    grid index maps return int32 zeros.

VMEM per step: the (8, Bk) key tile (3 rows padded to 8 sublanes), the
(Bq, 128) query tile (3 lanes padded to 128) and the (Bq x Bk) compare
tile. Defaults (Bq=256, Bk=2048) ≈ 2.3 MB — inside the 16 MB scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_I32_MAX = np.iinfo(np.int32).max


def _less3(a0, a1, a2, b0, b1, b2):
    """Lexicographic (a0,a1,a2) < (b0,b1,b2), elementwise."""
    return (a0 < b0) | ((a0 == b0) & ((a1 < b1) | ((a1 == b1) & (a2 < b2))))


def _zero(i):
    """An int32 zero for an index map: a literal 0 would be int64 under
    jax_enable_x64, which Mosaic cannot return from the map."""
    return i * 0


def key_rows(k_ref):
    """The three (1, Bk) component rows of a lane-major key block."""
    return k_ref[0:1, :], k_ref[1:2, :], k_ref[2:3, :]


def query_cols(q_ref):
    """The three (Bq, 1) component columns of a sublane-major probe block."""
    return q_ref[:, 0:1], q_ref[:, 1:2], q_ref[:, 2:3]


def count_less(k, q):
    """(Bq, 1) int32: how many of the block's keys are < each query."""
    lt = _less3(k[0], k[1], k[2], q[0], q[1], q[2])        # (Bq, Bk)
    return jnp.sum(lt.astype(jnp.int32), axis=1, keepdims=True,
                   dtype=jnp.int32)


def block_tests(k, lo0, hi0, block_k: int):
    """Per-probe tests of a key block on the leading column: a range
    [lo, hi) whose leading id is above the block's largest (every key <
    lo) bumps its rank carries by ``block_k``; one whose ``hi`` leading id
    is below the block's smallest (every key > hi) adds nothing. The tests
    are conservative — a tie on the leading id is left to the elementwise
    tile. Returns (bump (Bq, 1) int32, work: does any probe need the tile)."""
    above = lo0 > jnp.max(k[0])
    below = hi0 < jnp.min(k[0])
    work = jnp.max(jnp.where(above | below, np.int32(0), np.int32(1))) > 0
    return jnp.where(above, np.int32(block_k), np.int32(0)), work


def _kernel(k_ref, q_ref, out_ref, *, block_k: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k, q = key_rows(k_ref), query_cols(q_ref)
    bump, work = block_tests(k, q[0], q[0], block_k)

    @pl.when(jnp.logical_not(work))
    def _skip():
        out_ref[...] = out_ref[...] + bump

    @pl.when(work)
    def _boundary():
        out_ref[...] = out_ref[...] + count_less(k, q)


def pad_keys(keys3: jax.Array, block_k: int) -> jax.Array:
    """(M, 3) sorted rows -> lane-major (3, M') padded with INT32_MAX
    sentinel columns to a multiple of block_k."""
    pad_k = (-keys3.shape[0]) % block_k
    keys_t = keys3.astype(jnp.int32).T
    if pad_k:
        keys_t = jnp.pad(keys_t, ((0, 0), (0, pad_k)),
                         constant_values=_I32_MAX)
    return keys_t


def searchsorted3(keys3: jax.Array, queries3: jax.Array, *,
                  block_k: int = 2048, block_q: int = 256,
                  interpret: bool = False) -> jax.Array:
    """keys3: (M, 3) int32 lexicographically sorted; queries3: (Q, 3)
    int32. Returns ranks (Q,) int32 ('left' semantics)."""
    q = queries3.shape[0]
    keys_t = pad_keys(keys3, block_k)
    queries3 = queries3.astype(jnp.int32)
    pad_q = (-q) % block_q
    if pad_q:
        queries3 = jnp.pad(queries3, ((0, pad_q), (0, 0)),
                           constant_values=_I32_MAX)
    nk = keys_t.shape[1] // block_k
    nq = queries3.shape[0] // block_q
    out = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k),
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((3, block_k), lambda i, j: (_zero(j), j)),
            pl.BlockSpec((block_q, 3), lambda i, j: (i, _zero(i))),
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda i, j: (i, _zero(i))),
        out_shape=jax.ShapeDtypeStruct((queries3.shape[0], 1), jnp.int32),
        interpret=interpret,
        name="searchsorted3",
    )(keys_t, queries3)
    return out[:q, 0]
