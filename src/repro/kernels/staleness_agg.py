"""Pallas kernel for the FedS3A aggregation inner sum (Eq. 10).

out = sum_k w_k * delta_k over K stacked client deltas, where w_k already
folds |D_i|/|D_Gk| * g(r - r_i) * participation. Fusing the weighted
reduction means ONE pass over the (K, N) stack instead of K separate
scaled-add passes (the server aggregates every round; for a 1.5B-param model
the stack is 10s of GB).

Grid: (ceil(N / 512),); block (K, 512) in VMEM — the block covers all K
rows, so any K tiles — with the weight vector (K, 1). The output is the
lane-dense (1, N) row, reshaped to (N,) by the wrapper; a partial tail block
needs no padding (its out-of-range columns are never written back).

Oracle: kernels/ref.py::staleness_agg_ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLK = 512


def _staleness_agg_kernel(d_ref, w_ref, o_ref):
    d = d_ref[...].astype(jnp.float32)               # (K, BLK)
    w = w_ref[...].astype(jnp.float32)               # (K, 1)
    o_ref[...] = jnp.sum(d * w, axis=0, keepdims=True)


def staleness_agg_pallas(deltas, weights, *, interpret=True):
    """deltas: (K, N), any N; weights: (K,). Returns (N,) fp32."""
    K, N = deltas.shape
    out = pl.pallas_call(
        _staleness_agg_kernel,
        grid=(pl.cdiv(N, BLK),),
        in_specs=[pl.BlockSpec((K, BLK), lambda i: (0, i)),
                  pl.BlockSpec((K, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, BLK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(deltas, weights.reshape(K, 1))
    return out.reshape(N)
