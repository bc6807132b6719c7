"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels compile with Mosaic; on any other backend (the CPU test
suite) they run in the Pallas interpreter. ``masked_pseudo_ce`` carries a
custom VJP so the FedS3A client loss is differentiable (backward is the
standard (p - onehot) * mask softmax grad).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.csr_compact import csr_compact2d_pallas
from repro.kernels.csr_quant import csr_quantize2d_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.masked_pseudo_ce import masked_pseudo_ce_pallas
from repro.kernels.ref import csr_decode_ref
from repro.kernels.sparse_delta import (sparse_delta2d_pallas,
                                        sparse_delta2d_quantile_pallas,
                                        sparse_delta_pallas)
from repro.kernels.staleness_agg import staleness_agg_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, window=None, causal=True):
    """q: (B,S,Hq,hd); k/v: (B,S,Hkv,hd) — GQA KV broadcast handled here."""
    G = q.shape[2] // k.shape[2]
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=_interpret())


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def masked_pseudo_ce(logits, threshold):
    loss, mask = masked_pseudo_ce_pallas(logits, threshold,
                                         interpret=_interpret())
    return loss, mask


def _mpce_fwd(logits, threshold):
    loss, mask = masked_pseudo_ce(logits, threshold)
    return (loss, mask), (logits, mask)


def _mpce_bwd(threshold, res, g):
    logits, mask = res
    g_loss = g[0]
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                            dtype=jnp.float32)
    d = (p - onehot) * (mask * g_loss)[:, None]
    return (d.astype(logits.dtype),)


masked_pseudo_ce.defvjp(_mpce_fwd, _mpce_bwd)


def sparse_delta(x, threshold):
    """Flattened delta -> (masked delta, per-512-block nnz). Tail padding
    (and its exclusion from the count) is handled inside the kernel wrapper."""
    return sparse_delta_pallas(x, threshold, interpret=_interpret())


def sparse_delta_batch(x, thresholds):
    """(K, N) stacked flat deltas x (K,) thresholds -> (masked (K, N),
    per-512-block nnz (K, nblk)) in ONE kernel launch over a 2D grid.

    Shard-safe: under the fleet engine's ``shard_map`` the (K, N) stack is
    the local client shard and the grid covers exactly its rows."""
    return sparse_delta2d_pallas(x, thresholds, interpret=_interpret())


def sparse_delta_topfrac(x, keep_frac):
    """Fused per-shard top-|.| sparsification: per-row sampled-quantile
    thresholds + 2D-grid mask/count, one dispatch. Returns
    (masked (K, N), nnz (K, nblk), thresholds (K,))."""
    return sparse_delta2d_quantile_pallas(x, keep_frac,
                                          interpret=_interpret())


def csr_compact(x, thresholds, cap):
    """(K, N) stacked flat deltas x (K,) thresholds -> the compacted CSR
    wire payload (values (K, cap) f32, indices (K, cap) int32, true nnz
    (K,) int32) in one grid launch (per-block counts -> exclusive scan ->
    in-kernel scatter). Per-row op, so shard-safe under the client mesh."""
    return csr_compact2d_pallas(x, thresholds, cap, interpret=_interpret())


def csr_quantize(values, indices, stored, n, *, q_dtype="int8"):
    """Quantize + index-pack a compacted CSR payload (``csr_q`` format):
    (values (K, cap) f32, indices (K, cap) int32, stored (K,) int32) ->
    (qvals (K, cap) int8|f16, offsets (K, cap) int16,
    block_counts (K, ceil(n/512)) int16, scales (K,) f32). Per-row op,
    shard-safe under the client mesh."""
    return csr_quantize2d_pallas(values, indices, stored, n,
                                 q_dtype=q_dtype, interpret=_interpret())


def csr_decode(values, indices, n):
    """Scatter-add decode of a CSR payload to dense (K, n) f32 rows.
    Padding slots hold value 0 at index 0 and scatter nothing."""
    return csr_decode_ref(values, indices, n)


def staleness_agg(deltas, weights):
    """(K, N) stacked deltas x (K,) weights -> (N,) fp32 weighted sum."""
    return staleness_agg_pallas(deltas, weights, interpret=_interpret())
