"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """q: (B,S,H,hd); k/v: (B,S,H,hd) (KV pre-broadcast to full heads)."""
    B, S, H, hd = q.shape
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(hd)
    if causal:
        qp = jnp.arange(S)
        mask = qp[None, :] <= qp[:, None]
        if window is not None:
            mask &= qp[None, :] > (qp[:, None] - window)
        logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def masked_pseudo_ce_ref(logits, threshold):
    """Paper Eq. 5: confidence-thresholded pseudo-label cross entropy.

    logits: (N, C). Returns (per_sample_loss (N,), mask (N,)).
    loss_i = 1[max softmax_i >= theta] * CE(argmax_i, softmax_i)
           = -mask_i * log(max_i softmax_i)
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    max_logp = jnp.max(logp, axis=-1)
    mask = (jnp.exp(max_logp) >= threshold).astype(jnp.float32)
    return -mask * max_logp, mask


def sparse_delta_ref(x, threshold):
    """Paper §IV-F: magnitude-threshold sparsification of a parameter delta.

    x: (N,) flattened delta, any N. Returns (masked (N,),
    nnz_per_block (ceil(N/512),)) with block size 512 (kernel tiling);
    pad columns never count, even for all-pass thresholds <= 0.
    """
    masked, nnz = sparse_delta2d_ref(x.reshape(1, -1),
                                     jnp.asarray(threshold).reshape(1))
    return masked.reshape(-1), nnz.reshape(-1)


def sparse_delta2d_ref(x, thresholds):
    """Batched §IV-F sparsification: one threshold per stacked client delta.

    x: (K, N) stacked flat deltas, any N; thresholds: (K,). Returns
    (masked (K, N), nnz_per_block (K, ceil(N/512)) int32), block size 512.
    The tail block's pad columns are excluded from the count (matching the
    kernel's in-kernel column guard).
    """
    blk = 512
    K, n = x.shape
    pad = (-n) % blk
    keep = jnp.abs(x) >= thresholds.reshape(K, 1)
    masked = jnp.where(keep, x, 0).astype(x.dtype)
    if pad:
        keep = jnp.concatenate(
            [keep, jnp.zeros((K, pad), keep.dtype)], axis=1)
    nnz = keep.reshape(K, (n + pad) // blk, blk).sum(axis=2).astype(jnp.int32)
    return masked, nnz


def csr_compact2d_ref(x, thresholds, cap):
    """Compacted CSR wire format for a stack of sparse deltas (§IV-F).

    x: (K, N) stacked flat deltas; thresholds: (K,); cap: static per-row
    payload capacity. Keeps ``(|x| >= thr) & (x != 0)`` — exact zeros pass
    the sparse-delta nnz *metric* at degenerate thresholds but carry no
    information, so they never go on the wire. Returns
    (values (K, cap) f32, indices (K, cap) int32, nnz (K,) int32): kept
    elements packed in ascending column order, zero-padded past
    ``min(nnz, cap)``; ``nnz`` is the true (uncapped) count, so overflow is
    detectable. Rank >= cap overflows off the payload (the comm layer
    spills it into the error-feedback residual).
    """
    K, n = x.shape
    thresholds = jnp.asarray(thresholds, jnp.float32).reshape(K, 1)
    keep = (jnp.abs(x.astype(jnp.float32)) >= thresholds) & (x != 0)
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=1)        # 1-based
    nnz = rank[:, -1]
    # slot s holds the s-th survivor; its column is the first index where
    # the running rank reaches s — a vmapped binary search over the
    # monotone rank vector (an argsort of the drop mask gives the same
    # columns but XLA:CPU sorts measured 7x slower)
    slots = jnp.arange(1, cap + 1, dtype=jnp.int32)
    cols = jax.vmap(lambda r: jnp.searchsorted(r, slots, side="left"))(rank)
    valid = slots[None, :] <= jnp.minimum(nnz, cap)[:, None]
    idx = jnp.where(valid, cols, 0).astype(jnp.int32)
    vals = jnp.where(valid, jnp.take_along_axis(x, idx, axis=1), 0.0)
    return vals.astype(jnp.float32), idx, nnz


def csr_capped_mask_ref(x, thresholds, cap):
    """Dense equivalent of ``csr_decode_ref(*csr_compact2d_ref(...))``:
    survivors whose in-row rank (column order) fits the capacity, everything
    else zeroed. Identical output to the compact -> scatter-decode
    round-trip, but pure elementwise/cumsum ops — no scatter, which XLA:CPU
    executes serially. The engines use this for the dense reconstruction
    (client upload models, distribute targets, residual expansion) while
    the payload arrays themselves feed accounting and the fused
    aggregation; on the distribute path, where only the stored counts are
    consumed, XLA dead-code-eliminates the compaction sort entirely.
    Returns (decoded (K, n), stored per-row counts (K,) int32).
    """
    K, n = x.shape
    thresholds = jnp.asarray(thresholds, jnp.float32).reshape(K, 1)
    keep = (jnp.abs(x.astype(jnp.float32)) >= thresholds) & (x != 0)
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=1)        # 1-based
    decoded = jnp.where(keep & (rank <= cap), x, 0).astype(jnp.float32)
    stored = jnp.minimum(keep.sum(axis=1), cap).astype(jnp.int32)
    return decoded, stored


def csr_decode_ref(values, indices, n):
    """Scatter-add decode of a CSR payload back to dense (K, n) rows.

    Invalid (padding) slots carry value 0 at index 0, so they scatter
    nothing. Round-trip contract: with cap >= nnz,
    ``csr_decode_ref(*csr_compact2d_ref(x, thr, cap)[:2], n)`` equals the
    masked-dense oracle ``sparse_delta2d_ref(x, thr)[0]`` exactly.
    """
    K = values.shape[0]
    rows = jnp.arange(K, dtype=jnp.int32)[:, None]
    return jnp.zeros((K, n), jnp.float32).at[rows, indices].add(
        values.astype(jnp.float32))


def csr_quant_scales_ref(values, stored, *, q_dtype="int8"):
    """Per-row (scales, reciprocals) of the ``csr_q`` quantizer.

    values: (K, cap) packed f32 payload values; stored: (K,) int32 valid
    prefix lengths. ``scale = absmax / 127`` over the stored prefix and
    ``inv = 1 / scale`` (0 for an all-zero row); fp16 rows carry ones.
    """
    K, cap = values.shape
    if q_dtype == "fp16":
        ones = jnp.ones((K,), jnp.float32)
        return ones, ones
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < \
        jnp.asarray(stored, jnp.int32)[:, None]
    v = jnp.where(valid, values.astype(jnp.float32), 0.0)
    scale = jnp.max(jnp.abs(v), axis=1) / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    return scale, inv


def csr_quantize2d_ref(values, stored, *, q_dtype="int8"):
    """Per-row absmax quantization of packed CSR values (``csr_q`` format).

    values: (K, cap) packed f32 payload values; stored: (K,) int32 valid
    prefix lengths. Returns (qvals (K, cap), scales (K,) f32):

    * ``q_dtype="int8"``: ``scale = absmax / 127`` over the stored prefix
      (padding slots are already zero and cannot raise the absmax);
      ``q = clip(round(v / scale), -127, 127)``. An all-zero row gets
      scale 0 and an all-zero payload.
    * ``q_dtype="fp16"`` (fallback for deltas whose dynamic range int8
      cannot hold): values cast to float16, scales all-ones so the
      dequantize path ``q * scale`` is format-agnostic.

    Dequantization is intentionally lossy; the comm layer folds
    ``delta - dequant(decode(payload))`` into the error-feedback residual,
    so the loss is re-sent later rather than forgotten.
    """
    cap = values.shape[1]
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < \
        jnp.asarray(stored, jnp.int32)[:, None]
    v = jnp.where(valid, values.astype(jnp.float32), 0.0)
    scale, inv = csr_quant_scales_ref(values, stored, q_dtype=q_dtype)
    if q_dtype == "fp16":
        return v.astype(jnp.float16), scale
    q = jnp.clip(jnp.round(v * inv[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def csr_dequantize_ref(qvals, scales):
    """(K, cap) quantized payload values -> f32. fp16 payloads carry
    all-one scales, so one expression serves both value dtypes."""
    return qvals.astype(jnp.float32) * \
        jnp.asarray(scales, jnp.float32)[:, None]


def quantize_dense_ref(dense, scales, *, q_dtype="int8"):
    """Elementwise quantize->dequantize round-trip of a dense (K, n) row
    stack under the given per-row scales — the scatter-free twin of
    ``csr_decode_ref(csr_dequantize_ref(...))`` when ``dense`` is the
    capped-mask decode and ``scales`` came from the packed payload (the
    absmax over the stored prefix equals the absmax over the dense decode,
    and both paths round the identical quotients)."""
    if q_dtype == "fp16":
        return dense.astype(jnp.float16).astype(jnp.float32)
    s = jnp.asarray(scales, jnp.float32)[:, None]
    inv = jnp.where(s > 0, 1.0 / jnp.where(s > 0, s, 1.0), 0.0)
    q = jnp.clip(jnp.round(dense.astype(jnp.float32) * inv), -127, 127)
    return q * s


def csr_pack_indices_ref(indices, stored, n):
    """Pack (K, cap) absolute int32 CSR columns as per-block int16 offsets.

    Columns are ascending within each stored prefix (csr_compact contract),
    so elements of one 512-block are contiguous and a per-row block-count
    table recovers which block each slot belongs to. Returns
    (offsets (K, cap) int16 = col % 512 with padding zeroed,
    block_counts (K, nblk) int16 with nblk = ceil(n/512)).
    """
    blk = 512
    K, cap = indices.shape
    nblk = max((n + blk - 1) // blk, 1)
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < \
        jnp.asarray(stored, jnp.int32)[:, None]
    offs = jnp.where(valid, indices % blk, 0).astype(jnp.int16)
    blk_id = jnp.where(valid, indices // blk, nblk)   # pad -> past the end
    # blk_id ascends along each row, so the number of slots in blocks <= b
    # is a binary search: counts are the differences of that cumulative
    # histogram, O(nblk log cap) per row. The scope is the wire's slot-search
    # leaf (core/telemetry.py COMPACT), on the ref and Pallas paths alike.
    with jax.named_scope("compact"):
        upto = jax.vmap(lambda r: jnp.searchsorted(
            r, jnp.arange(nblk, dtype=jnp.int32), side="right"))(blk_id)
    counts = jnp.diff(upto, axis=1, prepend=0)
    return offs, counts.astype(jnp.int16)


def csr_unpack_indices_ref(offsets, block_counts):
    """Reconstruct absolute int32 columns from the packed ``csr_q`` index
    encoding: slot s lives in the first block whose cumulative count
    exceeds s (vmapped binary search, same idiom as csr_compact2d_ref).
    Padding slots resolve past the last block; they are clamped into range
    (their values are zero, so the scatter-add they feed adds nothing).
    """
    K, cap = offsets.shape
    nblk = block_counts.shape[1]
    cum = jnp.cumsum(block_counts.astype(jnp.int32), axis=1)
    slots = jnp.arange(cap, dtype=jnp.int32)
    blk_id = jax.vmap(
        lambda c: jnp.searchsorted(c, slots, side="right"))(cum)
    blk_id = jnp.minimum(blk_id, nblk - 1)
    return blk_id.astype(jnp.int32) * 512 + offsets.astype(jnp.int32)


def csr_row_ptr_ref(nnz_stored):
    """(K,) stored per-row counts -> the (K+1,) CSR row pointer."""
    nnz_stored = jnp.asarray(nnz_stored, jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(nnz_stored)])


def staleness_agg_ref(deltas, weights):
    """Paper Eq. 10 inner sum: staleness/size-weighted client aggregation.

    deltas: (K, N) stacked client deltas; weights: (K,) already containing
    |D_i|/|D_G| * g(r - r_i) * participation mask. Returns (N,) fp32.
    """
    return jnp.einsum("k,kn->n", weights.astype(jnp.float32),
                      deltas.astype(jnp.float32))
