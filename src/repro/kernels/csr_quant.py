"""Pallas kernel quantizing + index-packing CSR payloads (``csr_q`` format).

The csr_compact kernel materializes the f32 CSR wire payload — values
(K, cap) f32 + absolute column indices (K, cap) int32, 8 bytes per stored
element. This kernel compresses that payload in place:

* values -> int8 with a per-row absmax scale (``scale = absmax / 127``,
  ``q = clip(round(v / scale), -127, 127)``; an all-zero row gets scale 0),
  or float16 when the caller opts into the wide-dynamic-range fallback;
* absolute columns -> int16 in-block offsets (``col % 512``). csr_compact
  emits columns in ascending order, so the elements of each 512-block are
  contiguous in the payload and a per-row (nblk,) block-count table — the
  same per-block nnz csr_compact's stage 1 already computes — recovers the
  block id of every slot (ref.py::csr_unpack_indices_ref). 512 < 2^15, so
  int16 offsets are exact.

Wire cost per stored element drops from 8 bytes (f32 + int32) to 3 (int8 +
int16), plus 4 bytes/row of scale and 2*ceil(n/512) bytes/row of block
table. Quantization is lossy BY DESIGN: the comm layer computes the
residual against the dequantized decode, so the rounding error joins the
sparsification overflow in the error-feedback store and is re-sent later.

Grid: (ceil(K / 32), ceil(cap / 2048)); blocks (rb, 2048) with
rb = min(K, 32) — the int8 sublane tile (32, also a multiple of int16's 16),
or all K rows. The per-row absmax scale needs the whole row, so the wrapper
computes it (one XLA reduction over the packed values, the oracle's own
expression) and hands the kernel the (K, 1) reciprocal and stored counts; the
kernel is then one fused elementwise pass (mask + scale + round + lane
offset). The fp16 fallback's cast runs in XLA after the kernel: Mosaic has
no f32 -> f16 vector pack on v5e. The block-count table (a per-row binary
search over the ascending block ids) is also built in the wrapper.
Oracle: ref.py::csr_quantize2d_ref / csr_pack_indices_ref.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import csr_pack_indices_ref, csr_quant_scales_ref

BLK = 512
ROWS = 32             # int8 sublane tile (int16 needs 16, f32 8)
COLS = 2048


def _csr_quant_kernel(q_dtype, cb, vals_ref, idx_ref, stored_ref, inv_ref,
                      q_ref, off_ref):
    j = pl.program_id(1)
    v = vals_ref[...].astype(jnp.float32)                # (rb, cb)
    slot = j * cb + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    valid = slot < stored_ref[...]
    v = jnp.where(valid, v, 0.0)
    if q_dtype == "fp16":
        q_ref[...] = v              # v5e has no f16 vector pack: cast in XLA
    else:
        q = jnp.clip(jnp.round(v * inv_ref[...]), -127, 127)
        q_ref[...] = q.astype(jnp.int8)
    off = jnp.bitwise_and(idx_ref[...], BLK - 1)         # col % 512, col >= 0
    off_ref[...] = jnp.where(valid, off, 0).astype(jnp.int16)


def csr_quantize2d_pallas(values, indices, stored, n, *, q_dtype="int8",
                          interpret=True):
    """values: (K, cap) f32 packed payload values; indices: (K, cap) int32
    absolute columns (ascending per stored prefix); stored: (K,) int32 valid
    prefix lengths; n: the dense row width the indices address.

    Returns (qvals (K, cap) int8|f16, offsets (K, cap) int16,
    block_counts (K, ceil(n/512)) int16, scales (K,) f32). Per-row op —
    shard-invariant under the client mesh.
    """
    assert q_dtype in ("int8", "fp16"), q_dtype
    K, cap = values.shape
    stored = jnp.asarray(stored, jnp.int32)
    scales, inv = csr_quant_scales_ref(values, stored, q_dtype=q_dtype)
    rb = min(K, ROWS)
    cb = min(cap, COLS)                      # a multiple of 128, or all cap
    out_dtype = jnp.float32 if q_dtype == "fp16" else jnp.int8
    row_block = pl.BlockSpec((rb, cb), lambda i, j: (i, j))
    row_scalar = pl.BlockSpec((rb, 1), lambda i, j: (i, 0))
    qvals, offs = pl.pallas_call(
        partial(_csr_quant_kernel, q_dtype, cb),
        grid=(pl.cdiv(K, rb), pl.cdiv(cap, cb)),
        in_specs=[row_block, row_block, row_scalar, row_scalar],
        out_specs=[row_block, row_block],
        out_shape=[jax.ShapeDtypeStruct((K, cap), out_dtype),
                   jax.ShapeDtypeStruct((K, cap), jnp.int16)],
        interpret=interpret,
    )(values, indices, stored.reshape(K, 1), inv.reshape(K, 1))
    if q_dtype == "fp16":
        qvals = qvals.astype(jnp.float16)
    _, counts = csr_pack_indices_ref(indices, stored, n)
    return qvals, offs, counts, scales
