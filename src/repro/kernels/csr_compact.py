"""Pallas kernel compacting sparse-delta rows into CSR payloads (§IV-F).

The sparse-delta kernels mask a (K, N) stack of client deltas and count the
survivors, but the masked output is still DENSE — the comm layer merely
*accounted* nnz * 8 bytes while moving (K, N) floats. This kernel materializes
the actual wire payload: per client row, the kept elements are packed into a
``(cap,)`` values buffer and a matching ``(cap,)`` int32 column-index buffer
(ascending column order), so bytes-on-wire is the real size of real arrays
(values + indices + the derived row_ptr), not a promise.

Pipeline (matching the compaction plan the sparse-delta kernel's per-block
nnz output was designed for):

1. per-block keep counts — one cheap jnp pass over the (K, N) stack
   (``keep = (|x| >= thr) & (x != 0)``; exact zeros carry no information and
   never go on the wire, unlike the sparse-delta nnz metric which counts
   every threshold survivor);
2. in-kernel pack on a ``(ceil(K / rb), ceil(N / 512))`` grid of (rb, 512)
   blocks (rb = min(K, sublane tile): 8 rows for f32 input, 16 for bf16):
   each block ranks its kept elements with a (rb, 512) @ (512, 512)
   triangular-ones matmul (an inclusive in-block count — 0/1 operands, exact
   at any MXU precision), then packs them into the front of a 512-wide
   window: slot p takes the column that ``#{c : rank[c] <= p}`` names and
   the value that a one-hot matmul at HIGHEST precision selects (one nonzero
   term per slot, so the f32 value comes through exactly). Mosaic has no
   vector scatter, so the pack is the MXU-friendly stream-compaction idiom.
   Each block writes its window to its own 512 lanes of a (K, nblk * 512)
   output — lane-aligned, no dynamic offsets;
3. placement in XLA: slot s of a row lives in the block whose inclusive
   count first exceeds s (``ref.slot_buckets`` over the inclusive scan of
   the step-1 counts: one mark per block, one running sum over the slots),
   at lane ``s - offset[block]`` of that block's window.

Capacity/overflow contract: ``cap`` is the static per-row payload capacity.
Elements with global rank >= cap fall off the end of the buffer — the
wrapper zero-masks every slot >= ``min(nnz, cap)``, and the comm layer
spills the dropped mass into the error-feedback residual (or drops it,
matching the paper's lossy scheme, when EF is off). The returned ``nnz`` is
the TRUE per-row count, so callers can detect overflow (``nnz > cap``).

Oracle: kernels/ref.py::csr_compact2d_ref / csr_decode_ref.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import slot_buckets

BLK = 512


def _csr_pack_kernel(n_valid, x_ref, thr_ref, vals_ref, idx_ref):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                 # (rb, BLK)
    col = j * BLK + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    keep = (jnp.abs(x) >= thr_ref[...]) & (x != 0.0) & (col < n_valid)
    x = jnp.where(keep, x, 0.0)
    sub = jax.lax.broadcasted_iota(jnp.int32, (BLK, BLK), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (BLK, BLK), 1)
    # inclusive in-block rank: rank[c] = #{c' <= c : keep[c']}
    rank = jnp.dot(keep.astype(jnp.float32), (sub <= lane).astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    ones = jnp.ones((1, BLK), jnp.float32)
    nt = (((1,), (1,)), ((), ()))                      # contract lanes of both
    for r in range(x.shape[0]):
        rank_r = rank[r:r + 1, :]                  # (1, c), broadcast over p
        # slot p's column: the number of columns whose rank is <= p
        below = (rank_r <= sub.astype(jnp.float32)).astype(jnp.float32)
        pos = jax.lax.dot_general(ones, below, nt,
                                  preferred_element_type=jnp.float32)
        # slot p's value: the kept column whose inclusive rank is p + 1
        onehot = ((rank_r == (sub + 1).astype(jnp.float32))
                  & keep[r:r + 1, :]).astype(jnp.float32)
        val = jax.lax.dot_general(x[r:r + 1, :], onehot, nt,
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        vals_ref[r:r + 1, :] = val
        idx_ref[r:r + 1, :] = j * BLK + pos.astype(jnp.int32)


def csr_compact2d_pallas(x, thresholds, cap, *, interpret=True):
    """x: (K, N) stacked flat deltas, any N; thresholds: (K,); cap: static
    per-row payload capacity (1 <= cap <= N).

    Returns (values (K, cap) f32, indices (K, cap) int32, nnz (K,) int32):
    row k's kept elements (``|x| >= thr_k`` and nonzero) packed in ascending
    column order, zero-padded past ``min(nnz_k, cap)``; ``nnz`` is the true
    (uncapped) count. Per-row op — shard-invariant under a client mesh.
    """
    K, N = x.shape
    cap = int(cap)
    assert 1 <= cap <= N, (cap, N)
    nblk = pl.cdiv(N, BLK)
    thr = jnp.asarray(thresholds, jnp.float32).reshape(K, 1)
    # stage 1: per-block keep counts
    keep = (jnp.abs(x.astype(jnp.float32)) >= thr) & (x != 0)
    keep = jnp.pad(keep, ((0, 0), (0, nblk * BLK - N)))
    blocks = keep.reshape(K, nblk, BLK).sum(axis=2, dtype=jnp.int32)
    # stage 2: per-block packed windows
    rb = min(K, 32 // x.dtype.itemsize)     # sublane tile of x's dtype
    win = pl.BlockSpec((rb, BLK), lambda i, j: (i, j))
    vals_w, idx_w = pl.pallas_call(
        partial(_csr_pack_kernel, N),
        grid=(pl.cdiv(K, rb), nblk),
        in_specs=[win, pl.BlockSpec((rb, 1), lambda i, j: (i, 0))],
        out_specs=[win, win],
        out_shape=[jax.ShapeDtypeStruct((K, nblk * BLK), jnp.float32),
                   jax.ShapeDtypeStruct((K, nblk * BLK), jnp.int32)],
        interpret=interpret,
    )(x, thr)
    # stage 3: slot s sits in the first block whose inclusive count > s
    incl = jnp.cumsum(blocks, axis=1)
    nnz = incl[:, -1]
    slots = jnp.arange(cap, dtype=jnp.int32)
    blk = jnp.minimum(slot_buckets(incl, cap), nblk - 1)
    first = jnp.take_along_axis(incl - blocks, blk, axis=1)
    src = jnp.clip(blk * BLK + slots[None, :] - first, 0, nblk * BLK - 1)
    valid = slots[None, :] < jnp.minimum(nnz, cap)[:, None]
    vals = jnp.where(valid, jnp.take_along_axis(vals_w, src, axis=1), 0.0)
    idx = jnp.where(valid, jnp.take_along_axis(idx_w, src, axis=1), 0)
    return vals, idx, nnz
