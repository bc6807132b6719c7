"""Pallas kernel for the paper's pseudo-label loss (Eq. 5).

Fuses softmax + confidence threshold + pseudo-label CE into a single VMEM
pass over the logits: loss_i = -1[max p_i >= theta] * log(max_c p_ic).
The unfused jnp version makes three HBM round-trips over (N, C) logits
(softmax, max, gather); on large unlabeled client batches this layer is the
training hot spot of the FedS3A client step.

Grid: (ceil(N / blk),) over the transposed logits: the wrapper hands the
kernel (C, N), so a block (C, blk) holds blk samples along the 128 lanes and
the class reductions run down the sublanes. Loss and mask come out as
lane-dense (1, N) rows, reshaped to (N,) by the wrapper. The block covers
all C classes, so no class padding is needed, and a partial tail block's
out-of-range samples are never written back.

Oracle: kernels/ref.py::masked_pseudo_ce_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pseudo_ce_kernel(logits_ref, loss_ref, mask_ref, *, threshold):
    x = logits_ref[...].astype(jnp.float32)          # (C, blk)
    m = jnp.max(x, axis=0, keepdims=True)
    max_logp = -jnp.log(jnp.sum(jnp.exp(x - m), axis=0, keepdims=True))
    mask = (jnp.exp(max_logp) >= threshold).astype(jnp.float32)
    loss_ref[...] = -mask * max_logp
    mask_ref[...] = mask


def masked_pseudo_ce_pallas(logits, threshold, *, blk=256, interpret=True):
    """logits: (N, C). Returns (loss (N,), mask (N,))."""
    N, C = logits.shape
    blk = N if N <= blk else blk        # blk: a multiple of 128, or all N
    kernel = functools.partial(_pseudo_ce_kernel, threshold=threshold)
    loss, mask = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(N, blk),),
        in_specs=[pl.BlockSpec((C, blk), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, blk), lambda i: (0, i)),
                   pl.BlockSpec((1, blk), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)],
        interpret=interpret,
    )(logits.T)
    return loss.reshape(N), mask.reshape(N)
