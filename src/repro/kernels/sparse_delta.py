"""Pallas kernel for the paper's sparse-difference transmission (§IV-F).

Fuses |x| >= threshold masking with the per-block nonzero count in one VMEM
pass over the flattened parameter delta. The count feeds the ACO metric
(payload bytes / dense bytes) and the comm layer's compaction bookkeeping;
unfused, XLA reads the delta twice (mask, then reduce).

Three entry points share one kernel body:

* ``sparse_delta2d_pallas`` — the batched/sharded-round form: a (K, N) stack
  of K client deltas with a per-client threshold vector, masked and
  nnz-counted in a single call on a 2D grid ``(K, ceil(N / 512))``.
  Thresholds are runtime inputs (a (K, 1) block), so differing per-message
  quantile thresholds do NOT retrigger compilation and never touch the host.
  Under the fleet engine's ``shard_map`` the call sees only the local
  (K/D, N) client shard, so the grid is sized per shard and no cross-device
  traffic is generated — every row is masked against its own threshold.
* ``sparse_delta2d_quantile_pallas`` — fused per-shard top-|.| form: the
  strided-sample magnitude quantile per LOCAL row feeds the kernel as its
  threshold vector. Thresholds are a pure per-row statistic, so the result
  is invariant to how rows are sharded across devices.
* ``sparse_delta_pallas`` — the original single-delta form, the K=1 case.

Grid: (ceil(K / rb), ceil(N / 512)); x blocks (rb, 512) — 512 = 4 * 128
lanes, rb = min(K, sublane tile) rows (8 for f32, 16 for bf16) so the
second-minor block dimension is a multiple of the tile or covers all K
rows. Thresholds ride in an (rb, 1) block per grid row. The per-block counts are written lane-dense: the
(rb, 128) nnz output block of column-block j covers blocks
[128 * (j // 128), 128 * (j // 128) + 128) and stays resident in VMEM while
the sequential minor grid axis fills lane ``j % 128``. Partial tail blocks
(N not a multiple of 512, K not a multiple of rb) need no padding: the
kernel masks columns >= N out of the count (an in-kernel column-index
guard), so degenerate all-pass thresholds (thr <= 0) do not overcount, and
out-of-range rows and columns are never written back.

Oracle: kernels/ref.py::sparse_delta_ref / sparse_delta2d_ref.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLK = 512
LANES = 128
QUANTILE_SAMPLE = 2048


def _sparse_delta_kernel(n_valid, x_ref, thr_ref, out_ref, nnz_ref):
    j = pl.program_id(1)
    x = x_ref[...]                                   # (rb, BLK)
    col = j * BLK + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    keep = (jnp.abs(x.astype(jnp.float32)) >= thr_ref[...]) & (col < n_valid)
    out_ref[...] = jnp.where(keep, x, 0).astype(out_ref.dtype)
    cnt = jnp.sum(keep.astype(jnp.int32), axis=1, keepdims=True)   # (rb, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, nnz_ref.shape, 1)

    @pl.when(j % LANES == 0)
    def _():
        nnz_ref[...] = jnp.zeros_like(nnz_ref)

    nnz_ref[...] = jnp.where(lane == j % LANES, cnt, nnz_ref[...])


def sparse_delta2d_pallas(x, thresholds, *, interpret=True):
    """x: (K, N), any N; thresholds: (K,) runtime scalars.

    Returns (masked (K, N), nnz (K, ceil(N/512)) int32) — every client's
    delta is masked against its own threshold in one kernel launch. Columns
    past N (the tail block's pad) are excluded from the count in-kernel.
    """
    K, N = x.shape
    nblk = pl.cdiv(N, BLK)
    rb = min(K, 32 // x.dtype.itemsize)     # sublane tile of x's dtype
    thresholds = jnp.asarray(thresholds, jnp.float32).reshape(K, 1)
    masked, nnz = pl.pallas_call(
        partial(_sparse_delta_kernel, N),
        grid=(pl.cdiv(K, rb), nblk),
        in_specs=[pl.BlockSpec((rb, BLK), lambda i, j: (i, j)),
                  pl.BlockSpec((rb, 1), lambda i, j: (i, 0))],
        out_specs=[pl.BlockSpec((rb, BLK), lambda i, j: (i, j)),
                   pl.BlockSpec((rb, LANES), lambda i, j: (i, j // LANES))],
        out_shape=[jax.ShapeDtypeStruct((K, N), x.dtype),
                   jax.ShapeDtypeStruct((K, pl.cdiv(nblk, LANES) * LANES),
                                        jnp.int32)],
        interpret=interpret,
    )(x, thresholds)
    return masked, nnz[:, :nblk]


def local_quantile_thresholds(x, keep_frac, *, sample=QUANTILE_SAMPLE):
    """(K,) per-row |.|-quantile thresholds from a strided ``sample``-point
    subsample (matches sparse_comm's sampled-quantile semantics: an exact
    sort over millions of params per message dominates wall time; a 2k
    sample keeps the kept-fraction standard error under ~1%).

    Per-row statistic only — under ``shard_map`` each shard computes the
    thresholds of its local rows and the result matches the unsharded run.
    """
    K, N = x.shape
    stride = max(N // sample, 1)
    return jnp.quantile(jnp.abs(x[:, ::stride].astype(jnp.float32)),
                        1.0 - keep_frac, axis=1)


def sparse_delta2d_quantile_pallas(x, keep_frac, *, interpret=True):
    """Fused top-``keep_frac``-by-magnitude sparsification of a client shard.

    x: (K, N) local client deltas. Computes the per-row sampled-quantile
    threshold and feeds it straight into the 2D-grid kernel — one fused
    dispatch per shard, thresholds never leave the device. Returns
    (masked (K, N), nnz (K, ceil(N/512)), thresholds (K,)).
    """
    thr = local_quantile_thresholds(x, keep_frac)
    masked, nnz = sparse_delta2d_pallas(x, thr, interpret=interpret)
    return masked, nnz, thr


def sparse_delta_pallas(x, threshold, *, interpret=True):
    """x: (N,), any N. Returns (masked (N,), nnz (ceil(N/512),) int32).

    ``threshold`` may be a python float or a device scalar — it is a runtime
    input either way (no recompile per distinct threshold).
    """
    N = x.shape[0]
    thr = jnp.asarray(threshold, jnp.float32).reshape(1)
    masked, nnz = sparse_delta2d_pallas(x.reshape(1, N), thr,
                                        interpret=interpret)
    return masked.reshape(N), nnz.reshape(-1)
