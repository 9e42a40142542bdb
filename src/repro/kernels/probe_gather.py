"""Pallas TPU kernel: fused MAPSIN probe — the index GET in one pass.

The MAPSIN inner loop (core/mapsin.py `probe`) was built from ~6 unfused
ops: two `searchsorted` launches (lo and hi ranks), a `(B, cap)` int64
gather, an `unpack3` into three more `(B, cap)` temporaries, and a chain of
residual-filter compares — every one a round trip through HBM.  This kernel
fuses rank-find, range gather, residual predicate push-down and per-probe
slot placement into a single pass over the sorted column-store, so the only
HBM traffic is the key stream in and the `(B, cap)` match block out.

Layout and algorithm (DESIGN.md §2, same substrate as searchsorted.py):

  * keys live as THREE int32 columns (index order), lane-major `(3, Bk)`
    per block; probe endpoints sublane-major `(Bq, 3)` — TPU has no native
    int64 vectors; lexicographic compare on 3 x int32 is pure VPU, and
    everything in the kernel stays int32 under `jax_enable_x64`.
  * grid = (Q blocks, K blocks), K minor, so each probe block walks the
    sorted index sequentially.  Two VMEM scratch accumulators carry
    rank(lo) and rank(hi) across key blocks.
  * sortedness gives block pruning via per-probe bounds + `pl.when`
    (searchsorted.py `block_tests`): a probe whose range lies wholly above
    the block bumps both of its rank counters by `block_k`, one wholly
    below adds nothing, and only a block that some range meets pays the
    `(Bq, Bk)` compare tiles.
  * within such a block, the key at global position g is in probe q's
    range iff rank_q(lo) <= g < rank_q(hi), and then belongs to match slot
    c = g - rank_q(lo) (matches of a sorted range are contiguous), so
    placement is a one-hot accumulation over the slots the block fills —
    no gather, no scatter, no host-visible intermediate.  Residual
    equality filters (the HBase server-side predicate push-down) and
    intra-pattern variable repeats are applied in-register before a slot
    is marked valid.
  * per-probe overflow (`missed`) falls out of the final rank counters:
    max(rank(hi) - rank(lo) - cap, 0), written at the last key block.

VMEM per step: the (8, Bk) key tile, three (Bq, 128) probe tiles, the
(Bq x Bk) compare tiles and four (Bq, cap) output blocks.  Defaults (Bq=256,
Bk=2048, cap<=128) ≈ 7 MB — inside the 16 MB scoped VMEM.  The jnp path in
core/mapsin.py remains the validated reference (`impl="jnp"` vs
`"pallas_interpret"`); equivalence is asserted bit-exactly in
tests/test_probe_gather.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.searchsorted import (_zero, block_tests, count_less,
                                        key_rows, pad_keys, query_cols)


def _kernel(k_ref, lo_ref, hi_ref, flt_ref, out0_ref, out1_ref, out2_ref,
            val_ref, miss_ref, rlo_ref, rhi_ref, *, block_k: int, cap: int,
            nk: int, flt_mask: tuple, eq_positions: tuple):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for ref in (out0_ref, out1_ref, out2_ref, val_ref, miss_ref,
                    rlo_ref, rhi_ref):
            ref[...] = jnp.zeros_like(ref)

    k = key_rows(k_ref)                                  # 3 x (1, bk)
    lo, hi = query_cols(lo_ref), query_cols(hi_ref)      # 3 x (bq, 1)
    bump, work = block_tests(k, lo[0], hi[0], block_k)

    @pl.when(jnp.logical_not(work))
    def _skip():  # no range meets the block
        rlo_ref[...] = rlo_ref[...] + bump
        rhi_ref[...] = rhi_ref[...] + bump

    @pl.when(work)
    def _boundary():
        n_lo = count_less(k, lo)                         # (bq, 1)
        n_hi = count_less(k, hi)
        # rank(lo) is complete once this block is counted: every key < lo
        # precedes every in-range key in the sorted order
        start = rlo_ref[...] + n_lo                      # (bq, 1)
        idx = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)                  # (1, bk)
        slot = idx - start                               # (bq, bk)
        # in range <=> rank(lo) <= idx < rank(hi)
        ok = (slot >= 0) & (slot < cap) & (idx < rhi_ref[...] + n_hi)
        # residual predicate push-down, evaluated in-register
        for pos in range(3):
            if flt_mask[pos]:
                ok = ok & (k[pos] == flt_ref[:, pos:pos + 1])
        for a, b in eq_positions:
            ok = ok & (k[a] == k[b])
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)

        def place(c, carry):
            sel = ok & (slot == c)                       # (bq, bk)
            col = lanes == c                             # (1, cap)
            for pos, ref in enumerate((out0_ref, out1_ref, out2_ref)):
                v = jnp.sum(jnp.where(sel, k[pos], np.int32(0)), axis=1,
                            keepdims=True, dtype=jnp.int32)
                ref[...] = ref[...] + jnp.where(col, v, np.int32(0))
            nv = jnp.sum(sel.astype(jnp.int32), axis=1, keepdims=True,
                         dtype=jnp.int32)
            val_ref[...] = val_ref[...] + jnp.where(col, nv, np.int32(0))
            return carry

        # only the slots this block fills: ranges are contiguous, so the
        # block's hits occupy one interval of slots per probe
        c_lo = jnp.min(jnp.where(ok, slot, np.int32(cap)))
        c_hi = jnp.max(jnp.where(ok, slot, np.int32(-1))) + 1
        jax.lax.fori_loop(c_lo, c_hi, place, np.int32(0))
        rlo_ref[...] = start
        rhi_ref[...] = rhi_ref[...] + n_hi

    @pl.when(j == nk - 1)
    def _finish():
        miss_ref[...] = jnp.maximum(rhi_ref[...] - rlo_ref[...] - cap,
                                    np.int32(0))


def probe_gather3(keys3: jax.Array, lo3: jax.Array, hi3: jax.Array,
                  flt3: jax.Array, *, cap: int,
                  flt_mask: tuple = (False, False, False),
                  eq_positions: tuple = (),
                  block_k: int = 2048, block_q: int = 256,
                  interpret: bool = False):
    """Fused probe over a sorted 3-column store.

    keys3: (M, 3) int32 lexicographically sorted; lo3/hi3: (B, 3) int32
    per-probe [lo, hi) range endpoints; flt3: (B, 3) int32 residual
    equality values (active where flt_mask[pos]).

    Returns (match3 (B, cap, 3) int32, valid (B, cap) bool, missed (B,)
    int32): slot c of probe b holds the (c+1)-th key of b's range (0 where
    invalid), valid marks slots whose key also passes the residual filters,
    missed counts range entries beyond `cap` ('left' rank semantics,
    residual-independent — identical to the jnp gather_range contract).
    """
    b = lo3.shape[0]
    keys_t = pad_keys(keys3, block_k)
    lo3, hi3, flt3 = (x.astype(jnp.int32) for x in (lo3, hi3, flt3))
    pad_b = (-b) % block_q
    if pad_b:
        pad = ((0, pad_b), (0, 0))
        lo3 = jnp.pad(lo3, pad)       # empty [0, 0) ranges
        hi3 = jnp.pad(hi3, pad)
        flt3 = jnp.pad(flt3, pad)
    nk = keys_t.shape[1] // block_k
    bq = lo3.shape[0]
    nq = bq // block_q
    probe_spec = pl.BlockSpec((block_q, 3), lambda i, j: (i, _zero(i)))
    slot_spec = pl.BlockSpec((block_q, cap), lambda i, j: (i, _zero(i)))
    out0, out1, out2, val, miss = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, cap=cap, nk=nk,
                          flt_mask=tuple(flt_mask),
                          eq_positions=tuple(eq_positions)),
        grid=(nq, nk),
        in_specs=[pl.BlockSpec((3, block_k), lambda i, j: (_zero(j), j)),
                  probe_spec, probe_spec, probe_spec],
        out_specs=[slot_spec] * 4 + [
            pl.BlockSpec((block_q, 1), lambda i, j: (i, _zero(i)))],
        out_shape=[jax.ShapeDtypeStruct((bq, cap), jnp.int32)] * 4 + [
            jax.ShapeDtypeStruct((bq, 1), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.int32),   # rank(lo) carry
            pltpu.VMEM((block_q, 1), jnp.int32),   # rank(hi) carry
        ],
        interpret=interpret,
        name="probe_gather3",
    )(keys_t, lo3, hi3, flt3)
    match3 = jnp.stack([out0, out1, out2], axis=-1)
    return match3[:b], (val[:b] > 0), miss[:b, 0]
